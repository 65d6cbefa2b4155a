"""C2P2SL as a TPU pipeline: micro-batch pipelining over the ``pod`` axis.

This is the paper's core insight transplanted to pods (DESIGN.md §3/§4):
the slow link is no longer a TDMA radio channel but the inter-pod DCN/ICI
boundary.  The first ``l`` layers ("UE-side model") live on pod 0, the rest
("BS-side model") on pod 1; each batch is split into ``k`` micro-batches
that stream through the stages.  The mapping:

    UE FP            -> stage-0 block scan on micro-batch m
    uplink (UT)      -> ppermute stage0 -> stage1 of the cut activations
    BS FP + BP 1F1B  -> stage-1 compute; jax.grad through the scan gives
                        the reverse pipeline
    downlink (DT)    -> the autodiff transpose of the forward ppermute
    gradient accumulation over k micro-batches -> the scan's grad sum

Implementation: a ``shard_map`` over the ``spec.axis`` ('pod') with a
``lax.scan`` over the pipeline ticks.  With ``virtual_stages == 1`` this
is the plain 1F1B schedule: ``k + S - 1`` ticks, stage s processes
micro-batch ``t - s`` at tick t; outputs move to stage ``s+1`` via
``ppermute`` — XLA's latency-hiding scheduler overlaps the transfer with
the next tick's compute, which is exactly the paper's
communication/computation overlap.

Interleaved (virtual-stage) scheduling generalizes this: with
``virtual_stages = v`` the layer stack splits into ``S*v`` chunks and
chunk c lives on physical stage ``c % S`` (round-robin), so each stage
owns v non-contiguous model chunks of ``L/(S*v)`` layers.  Micro-batch m
enters the pipeline at tick ``sigma(m) = (m // S)*S*v + (m % S)`` and
chunk c of micro-batch m runs at tick ``sigma(m) + c`` — the standard
interleaved spacing, provably collision-free on every stage (two chunks
of one stage differ by a multiple of S; two start offsets never do
unless they differ by >= S*v).  A tick now costs 1/v of a stage pass, so
the warm-up/drain bubble shrinks from ``(S-1)`` stage-passes to
``(S-1)/v`` per direction at the same k, at the price of v-1 extra
cut-activation hops per micro-batch (the chunk boundary wraps from stage
S-1 back to stage 0, hence the cyclic ppermute when v > 1).  The reverse
(backward) interleaved pipeline still falls out of ``jax.grad`` through
the scan — the transpose of a cyclic ppermute is the reverse cyclic
ppermute, and the transpose of the per-tick chunk gather is the
scatter-add into the right chunk's weight gradient.

The region is Manual over 'pod' ONLY — data/model axes stay GSPMD-auto
inside the stage, with an explicit constraint anchoring the micro-batch
to the data axis (without it GSPMD replicates the micro-batch across the
data axis: redundant compute on every data shard).

Embedding and LM head run replicated across pods (negligible FLOP share);
the ppermuted tensor is the cut-layer activation — the paper's ``s_l``.

``PipelineSpec.wire_dtype`` selects the wire codec for that hop
(``parallel/wire.py``): ``"int8"`` / ``"fp8"`` block-quantize the cut
activation before each forward ppermute and the activation gradient on
the transposed backward ppermute — EPSL's payload compression applied to
the pod boundary — while ``"none"`` keeps the raw ppermute bit-for-bit.
The codec wraps the hop only, inside ``_tick_loop``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models.blocks import apply_block
from repro.parallel import compat, wire
from repro.parallel.compat import PartitionSpec as P
from repro.parallel.context import ParallelCtx, use_ctx


@dataclasses.dataclass(frozen=True)
class PipelineSpec:
    num_stages: int = 2          # S: UE-side / BS-side (extensible)
    microbatches: int = 4        # k — pick with repro.core.ao.lemma1_k
    virtual_stages: int = 1      # v: interleaved model chunks per stage
    wire_dtype: str = "none"     # hop codec: none | int8 | fp8 (wire.py)
    axis: str = "pod"

    def __post_init__(self):
        # normalize the codec name at construction so every consumer
        # (the tick loop's coded-vs-raw branch, planners, logs) sees one
        # spelling; membership/availability is validated when the
        # pipeline actually runs (pipeline_blocks)
        norm = "none" if self.wire_dtype is None \
            else str(self.wire_dtype).strip().lower()
        object.__setattr__(self, "wire_dtype", norm)

    @classmethod
    def from_plan(cls, plan, *, axis: str = "pod") -> "PipelineSpec":
        """The sanctioned ``Plan -> PipelineSpec`` constructor.

        ``plan`` is the single plan currency (``analysis/autotune.Plan``)
        or its ``to_json()`` dict; every launcher builds its pipeline
        through here so a plan that changes mid-run (training/replan.py)
        and a plan fixed at launch construct identically.
        """
        from repro.analysis.autotune import Plan
        if isinstance(plan, dict):
            plan = Plan.from_json(plan)
        if not isinstance(plan, Plan):
            raise TypeError(
                f"from_plan expects an autotune.Plan (or its to_json() "
                f"dict), got {type(plan).__name__} — build one with "
                "Plan(stages=..., k=..., v=..., wire_dtype=...)")
        return cls(num_stages=plan.stages, microbatches=plan.k,
                   virtual_stages=plan.v, wire_dtype=plan.wire_dtype,
                   axis=axis)

    @property
    def plan(self):
        """This spec as the single plan currency (inverse of
        ``from_plan``; the pod axis name is runtime context, not plan)."""
        from repro.analysis.autotune import Plan
        return Plan(stages=self.num_stages, k=self.microbatches,
                    v=self.virtual_stages, wire_dtype=self.wire_dtype)

    @classmethod
    def auto_k(cls, stage_compute_s: float, link_s: float, *,
               num_stages: int = 2, virtual_stages: int = 1,
               k_cap: int = 16, axis: str = "pod"):
        """Spec with k chosen by the paper's Lemma 1 closed form
        (repro.core.ao.pipeline_k_auto) from per-stage compute time and
        inter-stage link time; interleaving (v > 1) divides the k needed
        to reach the steady state."""
        from repro.core.ao import pipeline_k_auto
        k = pipeline_k_auto(stage_compute_s, link_s, k_cap=k_cap,
                            virtual_stages=virtual_stages)
        return cls(num_stages=num_stages, microbatches=k,
                   virtual_stages=virtual_stages, axis=axis)

    @classmethod
    def auto_plan(cls, source, *, num_stages: int | None = None,
                  k_fixed: int | None = None, v_fixed: int | None = None,
                  wire_dtype: str | None = None,
                  axis: str = "pod", **extract_kwargs):
        """Spec with (k, v[, wire codec]) chosen by the roofline planner.

        ``source`` is a dry-run record dict (launch/dryrun.py JSONL), a
        ``repro.analysis.autotune.PlanInputs``, or an already-chosen
        ``AutoPlan``.  ``k_fixed`` / ``v_fixed`` pin one coordinate (a
        hand flag overriding half of an auto plan).  ``wire_dtype`` pins
        the hop codec ('none'/'int8'/'fp8'); ``'auto'`` asks the planner
        to enumerate the codec jointly with (k, v) — a smaller wire moves
        the argmin.  Returns ``(spec, AutoPlan)`` so callers can
        log/record the evidence.
        """
        from repro.analysis import autotune
        if isinstance(source, autotune.AutoPlan):
            if k_fixed is not None or v_fixed is not None \
                    or wire_dtype is not None:
                raise ValueError(
                    "k_fixed/v_fixed/wire_dtype cannot re-pin an "
                    "already-chosen AutoPlan — pass its PlanInputs "
                    "(plan.inputs) to re-plan with pins")
            plan = source
        else:
            inp = source
            if isinstance(source, dict):
                inp = autotune.plan_inputs_from_record(
                    source, num_stages=num_stages, **extract_kwargs)
            elif num_stages is not None and num_stages != inp.num_stages:
                inp = inp.with_stages(num_stages)
            wire_candidates = None
            if wire_dtype == "auto":
                wire_candidates = list(autotune.WIRE_AUTO)
            elif wire_dtype is not None:
                inp = inp.with_wire(wire_dtype)
            plan = autotune.choose_plan(inp, k_fixed=k_fixed,
                                        v_fixed=v_fixed,
                                        wire_candidates=wire_candidates)
        return cls.from_plan(plan.plan, axis=axis), plan


def _split_stages(blocks, num_stages: int, virtual_stages: int = 1):
    """[L, ...] stacked block params -> [S, v, L/(S*v), ...].

    Chunk ``c = j*S + s`` (layers ``[c*Lc, (c+1)*Lc)``) lands at
    ``out[s, j]`` — the round-robin placement of interleaved scheduling;
    ``v == 1`` degenerates to the contiguous S-way split.
    """
    chunks = num_stages * virtual_stages

    def r(a):
        l = a.shape[0]
        if l % chunks != 0:
            raise ValueError(
                f"num_layers {l} not divisible by num_stages x "
                f"virtual_stages = {num_stages} x {virtual_stages} = "
                f"{chunks} model chunks — pick S*v dividing the layer "
                "count")
        a = a.reshape((virtual_stages, num_stages, l // chunks)
                      + a.shape[1:])
        return jnp.swapaxes(a, 0, 1)
    return jax.tree.map(r, blocks)


def _sigma(m: int, num_stages: int, virtual_stages: int) -> int:
    """Pipeline-entry tick of micro-batch m (interleaved spacing).

    Consecutive micro-batches within a group of S enter back-to-back;
    groups are spaced S*v ticks apart so that no two chunks of one stage
    ever need the same tick (their chunk offsets differ by a multiple of
    S but less than S*v).  For v == 1 this is simply ``sigma(m) = m``.
    """
    return (m // num_stages) * num_stages * virtual_stages \
        + (m % num_stages)


def hop_perms(spec: PipelineSpec):
    """The tick schedule's inter-stage hop permutations on the pod axis:
    ``(forward, backward)`` tuples of ``(src, dst)`` pairs.

    This is the single source of truth the tick loop ships on — acyclic
    chain for v == 1 (the last stage has no successor), cyclic for v > 1
    (the chunk chain wraps from stage S-1 back to stage 0) — and the
    backward permutation is the transpose (reversed pairs), which is what
    ``wire.coded_ppermute``'s custom_vjp codes the gradient hop with.
    ``repro.analysis.staticcheck.expected_hop_perms`` mirrors it
    numpy-only so the auditor can verify lowered jaxpr/HLO against the
    schedule without importing this (jax-importing) module.
    """
    s = spec.num_stages
    if s <= 1:
        return (), ()
    if spec.virtual_stages > 1:
        fwd = tuple((i, (i + 1) % s) for i in range(s))
    else:
        fwd = tuple((i, i + 1) for i in range(s - 1))
    return fwd, tuple((dst, src) for src, dst in fwd)


def _check_mesh(mesh, spec: PipelineSpec):
    if spec.axis not in mesh.shape:
        raise ValueError(
            f"pipeline axis {spec.axis!r} not in mesh axes "
            f"{tuple(mesh.shape)} — build the mesh with a "
            f"{spec.axis!r} axis (launch/mesh.py)")
    if mesh.shape[spec.axis] != spec.num_stages:
        raise ValueError(
            f"num_stages={spec.num_stages} must equal the {spec.axis!r} "
            f"mesh axis size {mesh.shape[spec.axis]} (one stage per "
            f"{spec.axis} shard)")


def wire_ef_ticks(spec: PipelineSpec) -> int:
    """Tick count of one batch's schedule — the EF buffer's slot axis."""
    return _sigma(spec.microbatches - 1, spec.num_stages,
                  spec.virtual_stages) + spec.num_stages * spec.virtual_stages


def wire_ef_zeros(cfg, spec: PipelineSpec, batch: int, seq: int):
    """Zero-initialized error-feedback buffer for a top-k wire codec:
    f32 [S, ticks, mb, seq_total, d_model], one residual slot per
    (stage, tick) of the static schedule.  ``batch`` / ``seq`` are the
    RAW batch dims — padding (ragged k) and the vlm patch prefix are
    accounted for here exactly as ``make_pipelined_loss`` shapes the
    micro-batches.  Returns None when the codec carries no top-k (or
    S=1, where there is no hop)."""
    if spec.num_stages <= 1 or not wire.has_topk(spec.wire_dtype):
        return None
    k = spec.microbatches
    mb = (batch + (-batch) % k) // k
    seq_total = seq + (cfg.num_patches if cfg.family == "vlm" else 0)
    return jnp.zeros((spec.num_stages, wire_ef_ticks(spec), mb, seq_total,
                      cfg.d_model), jnp.float32)


def pipeline_blocks(cfg, blocks, xs, positions, spec: PipelineSpec, *,
                    mesh, prefix_len: int = 0, enc_outs=None, wire_ef=None):
    """Run the stacked homogeneous block stack as a pipeline.

    blocks: stacked params, leaves [L, ...]
    xs:     [k, mb, seq, d] micro-batched activations (embedded)
    enc_outs: optional [k, mb, enc_seq, d] (whisper cross-attention memory)
    wire_ef: [S, ticks, mb, seq, d] f32 error-feedback buffer, REQUIRED
             for top-k wire codecs at S > 1 (see ``wire_ef_zeros``); its
             gradient is the updated buffer.
    Returns (hidden [k, mb, seq, d], aux_loss scalar).

    The aux loss is the per-layer sum averaged over the k micro-batches —
    the same normalization as the plain (full-batch) forward, up to the
    documented per-micro-batch router-statistics deviation (DESIGN.md §6).
    """
    _check_mesh(mesh, spec)
    if spec.virtual_stages < 1:
        raise ValueError(
            f"virtual_stages={spec.virtual_stages} must be >= 1")
    wire.validate_wire_dtype(spec.wire_dtype)
    k = xs.shape[0]
    needs_ef = spec.num_stages > 1 and wire.has_topk(spec.wire_dtype)
    if needs_ef:
        if wire_ef is None:
            raise ValueError(
                f"wire_dtype {spec.wire_dtype!r} sparsifies the gradient "
                "hop with error feedback — build the EF buffer with "
                "pipeline.wire_ef_zeros and thread it through the loss "
                "(make_pipelined_loss / make_lm_train_step do this)")
        want = (spec.num_stages, wire_ef_ticks(spec)) + xs.shape[1:]
        if tuple(wire_ef.shape) != want:
            raise ValueError(
                f"wire_ef shape {tuple(wire_ef.shape)} != expected {want} "
                "([S, ticks, mb, seq, d] — rebuild with wire_ef_zeros "
                "after changing the spec or batch shape)")
    else:
        wire_ef = None
    staged = _split_stages(blocks, spec.num_stages, spec.virtual_stages)
    outs, auxes = _pipeline(cfg, staged, xs, positions, spec, mesh,
                            prefix_len, enc_outs, wire_ef)
    # last stage's real outputs; aux summed over stages (each owns its own
    # layers' aux), averaged over micro-batches
    return outs[-1], auxes.sum() / k


def _stage_scan_fn(cfg, spec, positions, prefix_len):
    """One stage's block scan on one micro-batch."""
    kind = cfg.layer_kinds[0]

    def stage_scan(blocks_local, x, enc_out, pin):
        def body(carry, layer_params):
            y, aux = apply_block(layer_params, carry, cfg, kind,
                                 positions=positions, prefix_len=prefix_len,
                                 enc_out=enc_out,
                                 use_rope=(kind != "rwkv"))
            return pin(y), aux
        y, auxes = jax.lax.scan(jax.checkpoint(body), pin(x), blocks_local)
        return y, auxes.sum()

    return stage_scan


def _tick_loop(spec, stage, k, xs_full, enc_full, state0, aux0, run_stage,
               wire_ef=None):
    """The (interleaved) 1F1B tick schedule of one stage.

    ``wire_ef`` (top-k codecs only) is this stage's error-feedback buffer
    [ticks, mb, seq, d] f32, entering the scan as per-tick xs so each
    hop's custom_vjp sees exactly its (stage, tick) slot; the scan's
    transpose reassembles the updated buffer as the gradient w.r.t. this
    input (parallel/wire.py::coded_ppermute_ef).

    At tick t stage s inverts the interleaved timetable: with
    ``t' = t - s``, ``p = t' mod S``, ``q = (t' - p) / S``, the live
    work item is micro-batch ``m = (q // v)*S + p`` on virtual chunk
    ``j = q mod v`` (global chunk ``j*S + s``), executing at its scheduled
    tick ``sigma(m) + j*S + s``.  Idle ticks (warm-up/drain, ragged k)
    compute on clipped indices and are masked by ``live`` — masked values
    are never consumed by a live tick because a live chunk's producer
    chunk was itself live one tick earlier.  Outputs move one stage
    forward via ``ppermute``; with v > 1 the chunk chain wraps from stage
    S-1 back to stage 0, so the permutation is cyclic.  Works for any
    S >= 1, v >= 1 and k >= 1 — ``pipeline_k_auto``-chosen k needs no
    divisibility with the stage count.
    """
    s_stages = spec.num_stages
    v = spec.virtual_stages
    ticks = _sigma(k - 1, s_stages, v) + s_stages * v
    fwd_perm, _ = hop_perms(spec)
    coded = spec.wire_dtype not in (None, "none")
    base_wire = spec.wire_dtype
    if coded:
        base_wire, _frac = wire.parse_wire_dtype(spec.wire_dtype)
        if _frac is None:
            wire_ef = None      # dense codec: no EF state to thread

    def hop(y, perm, ef_t):
        """One inter-stage hop: the raw ppermute (bit-identical to the
        uncoded pipeline), or the quantized wire round trip whose
        custom_vjp codes the transposed backward hop the same way —
        top-k + error feedback on that backward hop when ``ef_t`` rides
        along.  Its operations, the codec's and the backward hop's
        included, carry the ``pipeline.hop`` scope."""
        with jax.named_scope("pipeline.hop"):
            if not coded:
                return jax.lax.ppermute(y, spec.axis, perm)
            if ef_t is not None:
                return wire.coded_ppermute_ef(spec.wire_dtype, spec.axis,
                                              perm, y, ef_t)
            return wire.coded_ppermute(base_wire, spec.axis, perm, y)

    def tick(carry, xt):
        state, aux_acc = carry
        t, ef_t = xt if wire_ef is not None else (xt, None)
        tpr = t - stage
        p = jnp.mod(tpr, s_stages)
        q = (tpr - p) // s_stages
        j = jnp.mod(q, v)                      # this stage's virtual chunk
        m = (q // v) * s_stages + p            # this stage's micro-batch
        live = (tpr >= 0) & (m >= 0) & (m < k)
        m_idx = jnp.clip(m, 0, k - 1)
        j_idx = jnp.clip(j, 0, v - 1)
        inp0 = jax.lax.dynamic_index_in_dim(xs_full, m_idx, 0,
                                            keepdims=False)
        # only global chunk 0 (stage 0, virtual chunk 0) takes fresh
        # micro-batch input; every other chunk consumes the carried state
        cur = jnp.where((stage == 0) & (j_idx == 0), inp0, state)
        enc = None
        if enc_full is not None:
            enc = jax.lax.dynamic_index_in_dim(enc_full, m_idx, 0,
                                               keepdims=False)
        y, aux = run_stage(cur, enc, j_idx)
        if s_stages == 1:
            nxt = y                            # chunk chain stays local
        else:
            nxt = hop(y, fwd_perm, ef_t)
        aux_acc = aux_acc + jnp.where(live, aux, 0.0)
        return (nxt, aux_acc), y

    xs_scan = jnp.arange(ticks) if wire_ef is None \
        else (jnp.arange(ticks), wire_ef)
    (_, aux_acc), ys = jax.lax.scan(tick, (state0, aux0), xs_scan)
    # micro-batch m leaves the last chunk (on stage S-1) at tick
    # sigma(m) + S*v - 1; for v == 1 these are the contiguous ticks
    # [S-1, S-1+k) of the plain schedule
    out_ticks = jnp.asarray(
        [_sigma(m, s_stages, v) + s_stages * v - 1 for m in range(k)])
    out = jnp.take(ys, out_ticks, axis=0)
    return out, aux_acc


def _chunk_picker(blocks_local, virtual_stages: int):
    """``j -> one chunk's layer stack`` from [v, L/(S*v), ...] leaves.

    v == 1 resolves the (sole) chunk statically; v > 1 gathers the traced
    chunk index per tick — its autodiff transpose scatter-adds each
    tick's weight gradient into the right chunk.
    """
    if virtual_stages == 1:
        chunk0 = jax.tree.map(lambda a: a[0], blocks_local)
        return lambda j: chunk0
    return lambda j: jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, j, 0, keepdims=False),
        blocks_local)


def _pipeline(cfg, staged, xs, positions, spec, mesh, prefix_len, enc_outs,
              wire_ef=None):
    """The shard_map region: Manual over 'pod' (and size-1 axes),
    data/model auto."""
    k = xs.shape[0]
    # micro-batch over data; seq deliberately NOT model-sharded inside the
    # stage: per-micro-batch SP re-gathers the stage weights and re-reduces
    # weight grads k times (refuted, EXPERIMENTS.md §Perf pipeline it2) —
    # without SP, GSPMD defers the weight-grad reduction across ticks.
    # Axes of size 1 partition nothing: they run Manual with the pod axis,
    # so on a pipeline-only mesh the region is fully manual and Mosaic
    # kernels (the fused wire codec) can lower inside it.
    manual = {spec.axis} | {a for a, n in mesh.shape.items() if n == 1}
    data = None if "data" in manual or "data" not in mesh.shape else "data"
    data_sharding = compat.auto_axes_sharding(mesh, manual, P(data))

    def pin(x):
        """Anchor the micro-batch dim to the data axis INSIDE the manual-
        over-pod region — without this GSPMD replicates the micro-batch
        across the data axis (EXPERIMENTS.md §Perf, pipeline iteration 1)."""
        return jax.lax.with_sharding_constraint(x, data_sharding)

    stage_scan = _stage_scan_fn(cfg, spec, positions, prefix_len)

    def per_stage(blocks_stage, xs_full, enc_full, ef_full):
        # manual over 'pod': blocks_stage leaves [1, v, L/(S*v), ...]
        blocks_local = jax.tree.map(lambda a: a[0], blocks_stage)
        pick = _chunk_picker(blocks_local, spec.virtual_stages)
        stage = jax.lax.axis_index(spec.axis)
        # The replicated micro-batches (and encoder memory) meet stage-
        # varying values inside the tick loop.  Mark them varying HERE, at
        # entry: an implicit cast inside the loop transposes to a cross-pod
        # psum on every backward tick; at entry it is one psum per step.
        xs_full = compat.mark_varying(xs_full, (spec.axis,))
        if enc_full is not None:
            enc_full = compat.mark_varying(enc_full, (spec.axis,))
        # carries differ per stage -> mark them varying over the pod axis
        state = compat.mark_varying(
            jnp.zeros(xs_full.shape[1:], xs_full.dtype), (spec.axis,))
        aux0 = compat.mark_varying(jnp.float32(0.0), (spec.axis,))
        ef_local = None
        if ef_full is not None:
            # this stage's [ticks, mb, seq, d] slice; anchor the
            # micro-batch dim to the data axis like every other carry
            ef_local = jax.lax.with_sharding_constraint(
                ef_full[0],
                compat.auto_axes_sharding(mesh, manual, P(None, data)))
        out, aux_acc = _tick_loop(
            spec, stage, k, xs_full, enc_full, state, aux0,
            lambda cur, enc, j: stage_scan(pick(j), cur, enc, pin),
            wire_ef=ef_local)
        # stack a stage axis so out_specs=P('pod') can concatenate
        return out[None], aux_acc[None]

    args = [staged, xs]
    in_specs = [P(spec.axis), P()]
    if enc_outs is not None:
        args.append(enc_outs)
        in_specs.append(P())
    if wire_ef is not None:
        args.append(wire_ef)
        in_specs.append(P(spec.axis))

    def body(*a):
        i = 2
        enc_full = ef_full = None
        if enc_outs is not None:
            enc_full = a[i]
            i += 1
        if wire_ef is not None:
            ef_full = a[i]
        return per_stage(a[0], a[1], enc_full, ef_full)

    fn = compat.shard_map(
        body, mesh,
        in_specs=tuple(in_specs),
        out_specs=(P(spec.axis), P(spec.axis)),
        manual_axes=manual, check=True)
    return fn(*args)


def make_pipelined_loss(model, spec: PipelineSpec, mesh=None):
    """loss_fn(params, batch) with the block stack pipelined over pods.

    Requires a homogeneous (scan-stacked) architecture; the heterogeneous
    recurrentgemma pattern keeps the pod-as-DP path (DESIGN.md §7).

    Batches whose size is not divisible by k are padded with zero-
    embedding rows up to ``k * ceil(b / k)`` so ``pipeline_k_auto``-chosen
    k never needs batch-divisibility; pad rows are sliced off before the
    loss, so the xent is exactly the unpadded batch's for per-row
    architectures.  Caveat: MoE layers see the pad tokens (they shift the
    aux statistics and occupy shared capacity slots), one more facet of
    the documented per-micro-batch router deviation (DESIGN.md §6).
    """
    cfg = model.cfg
    assert cfg.homogeneous, (
        "pipeline mode needs a homogeneous layer stack; "
        f"{cfg.name} has a mixed pattern — use pod-as-data-parallel")
    k = spec.microbatches
    assert k >= 1, f"microbatches k={k} must be >= 1"

    def _loss(params, batch, wire_ef):
        # Plain-JAX context inside: data/model axes are GSPMD-auto, the
        # pipeline shard_map owns 'pod'.
        from repro.parallel.context import get_ctx
        use_mesh = mesh if mesh is not None else get_ctx().mesh
        with use_ctx(ParallelCtx()):
            dt = jnp.dtype(cfg.dtype)
            tokens = batch["tokens"]
            labels = batch["labels"]
            prefix_len = 0
            enc_flat = None

            x = model._embed(params, tokens, dt)
            if cfg.family == "vlm":
                patches = batch["patch_embeds"].astype(dt)
                x = jnp.concatenate([patches, x], axis=1)
                prefix_len = patches.shape[1]
                pad = jnp.full(patches.shape[:2], -1, labels.dtype)
                labels = jnp.concatenate([pad, labels], axis=1)
            if cfg.family == "audio":
                enc_flat = model._encode(params, batch["frames"].astype(dt))

            b, seq = x.shape[0], x.shape[1]
            pad_rows = (-b) % k
            if pad_rows:
                x = jnp.concatenate(
                    [x, jnp.zeros((pad_rows,) + x.shape[1:], x.dtype)])
                if enc_flat is not None:
                    enc_flat = jnp.concatenate(
                        [enc_flat, jnp.zeros((pad_rows,) + enc_flat.shape[1:],
                                             enc_flat.dtype)])
            mb = (b + pad_rows) // k
            xs = x.reshape(k, mb, seq, x.shape[-1])
            enc_outs = None
            if enc_flat is not None:
                enc_outs = enc_flat.reshape(k, mb, enc_flat.shape[1],
                                            enc_flat.shape[2])
            positions = jnp.arange(seq)

            out, aux = pipeline_blocks(cfg, params["blocks"], xs, positions,
                                       spec, mesh=use_mesh,
                                       prefix_len=prefix_len,
                                       enc_outs=enc_outs, wire_ef=wire_ef)
            h = out.reshape(b + pad_rows, seq, x.shape[-1])[:b]
            loss = model.head_loss(params, h, labels)
            total = loss + 0.01 * aux
            return total, {"xent": loss, "aux": aux}

    needs_ef = spec.num_stages > 1 and wire.has_topk(spec.wire_dtype)
    if needs_ef:
        # 3-arg loss: the EF buffer is an input whose GRADIENT is the
        # updated buffer (the hops' custom_vjp emits the new residuals as
        # the cotangent) — the train step extracts it with
        # value_and_grad(argnums=(0, 2)) and writes it back to state.
        def loss_fn(params, batch, wire_ef):
            return _loss(params, batch, wire_ef)
    else:
        def loss_fn(params, batch):
            return _loss(params, batch, None)
    loss_fn.needs_wire_ef = needs_ef
    return loss_fn
