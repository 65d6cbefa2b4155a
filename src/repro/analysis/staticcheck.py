"""Pipeline invariant auditor: jaxpr/HLO static analysis of the C2P2SL
pipeline's collectives, sharding leaks, and wire-byte honesty.

The pipeline's correctness rests on invariants nothing at runtime checks:

  * the 1F1B tick schedule must lower to collision-free BIJECTIVE
    ``ppermute``s whose permutation is exactly the schedule's hop
    (``pipeline.hop_perms`` forward, its transpose backward);
  * the wire codec (PR 5/6) must keep the coded hop at its declared
    element width — a single GSPMD reshard can silently re-inflate an
    int8 payload to f32 and void the planner's byte model;
  * no all-gather/all-reduce may cross the pod boundary INSIDE the tick
    loop (entry-level replicated-grad reductions are legitimate);
  * the planner's ``autotune.wire_bytes_per_element(_bwd)`` must equal
    what the compiled HLO actually ships per hop ("billed bytes ==
    compiled bytes") — the precondition for trustworthy adaptive
    re-planning (ROADMAP).

Three layers, composable and individually callable (tests exercise each
detector in isolation so one seeded defect yields exactly one violation):

  * **jaxpr audit** (``audit_jaxpr`` / ``audit_cells(level='jaxpr')``):
    traces ``make_pipelined_loss`` grads through ``compat.abstract_mesh``
    — device-free — and walks every
    (sub-)jaxpr for ppermute bijectivity/schedule, payload/index dtype
    contract, and pod-axis collective leaks.
  * **HLO audit** (``audit_hlo_text`` / ``audit_cells(level='hlo')``):
    parses compiled module text (``repro.analysis.hlo_costs``) scoped to
    while-reachable computations (the tick loops), checks device-level
    permutation bijectivity + pod-lifted schedule match, payload dtypes,
    cross-pod leaks, and reconciles per-tick hop bytes against the
    planner byte model.
  * **AST lint pack** (``repro.analysis.lint``): repo-specific rules ruff
    cannot express — tracer branching / concretization in
    ``_tick_loop``-reachable code, nested ``jax.jit``, ``pallas_call``
    without the ``interpret`` plumbing idiom.

CLI (the CI ``staticcheck`` job runs this)::

    python -m repro.analysis.staticcheck                 # jaxpr + lint + model
    python -m repro.analysis.staticcheck --level full    # + compiled-HLO audit
    python -m repro.analysis.staticcheck --lint [paths]  # lint only
    python -m repro.analysis.staticcheck --selftest      # seeded-violation corpus
    python -m repro.analysis.staticcheck --report out.json --diff \
        benchmarks/STATICCHECK_baseline.json

This module imports numpy only at module scope (jax lazily, inside the
audit functions) so ``--lint`` and the byte-model checks run before any
accelerator stack exists — same discipline as ``analysis/autotune.py``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

from repro.analysis import autotune, hlo_costs

#: Violation taxonomy (docs/staticcheck.md catalogs each class).
VIOLATION_CLASSES = (
    "ppermute-bijection",   # hop permutation is not a bijection
    "ppermute-schedule",    # hop permutation != the tick schedule's hop
    "sharding-leak",        # cross-pod collective inside the tick loop
    "wire-payload-dtype",   # coded-hop payload width != declared codec
    "wire-index-dtype",     # top-k index dtype != declared codec
    "vjp-residual-dtype",   # custom_vjp fwd/bwd residual contract broken
    "wire-bytes",           # compiled hop bytes != planner byte model
    "wire-bytes-model",     # autotune byte model != payload contract
    "lint",                 # AST rule pack finding (rule id in detail)
)

#: Canonical HLO spelling of each base codec's on-wire payload dtype —
#: numpy-only mirror of ``repro.kernels.wire_codec.PAYLOAD_HLO_DTYPE``
#: (that module imports jax/pallas); a tier-1 test pins the two copies.
#: fp8 payloads spell ``s8`` too: ``wire._wire_ppermute`` bitcasts
#: 1-byte float payloads to int8 around the collective precisely so a
#: backend without f8 collectives cannot re-inflate the hop to f16.
PAYLOAD_HLO_DTYPE = {"int8": "s8", "fp8": "s8"}

#: numpy/jax dtype name -> HLO element type (payload classification).
NP_TO_HLO_DTYPE = {
    "int8": "s8", "int16": "s16", "int32": "s32", "int64": "s64",
    "uint8": "u8", "uint16": "u16", "uint32": "u32",
    "float8_e4m3fn": "f8e4m3fn", "float8_e5m2": "f8e5m2",
    "bfloat16": "bf16", "float16": "f16", "float32": "f32",
    "float64": "f64", "bool": "pred",
}

_HLO_DTYPE_BYTES = dict(hlo_costs._DTYPE_BYTES)


@dataclasses.dataclass(frozen=True)
class Violation:
    """One classified invariant violation."""
    cls: str        # one of VIOLATION_CLASSES
    where: str      # cell / computation / file:line the finding anchors to
    detail: str     # human-readable defect statement

    def __post_init__(self):
        if self.cls not in VIOLATION_CLASSES:
            raise ValueError(
                f"unknown violation class {self.cls!r} — add it to "
                f"staticcheck.VIOLATION_CLASSES {VIOLATION_CLASSES}")

    def to_dict(self) -> dict:
        return {"class": self.cls, "where": self.where,
                "detail": self.detail}


def by_class(violations) -> dict:
    out: dict = {}
    for v in violations:
        out[v.cls] = out.get(v.cls, 0) + 1
    return out


# ---------------------------------------------------------------------------
# Schedule-level expectations (numpy-only mirror of pipeline.hop_perms).
# ---------------------------------------------------------------------------


def expected_hop_perms(num_stages: int, virtual_stages: int):
    """``(forward, backward)`` hop permutations of the tick schedule on
    the pod axis — numpy-only mirror of ``parallel.pipeline.hop_perms``
    (that module imports jax; a tier-1 test pins the two)."""
    s = int(num_stages)
    if s <= 1:
        return (), ()
    if int(virtual_stages) > 1:
        fwd = tuple((i, (i + 1) % s) for i in range(s))
    else:
        fwd = tuple((i, i + 1) for i in range(s - 1))
    return fwd, tuple((dst, src) for src, dst in fwd)


def check_perm_bijection(perm, axis_size: int, where: str = "perm"):
    """A hop permutation must be a partial bijection on [0, axis_size):
    unique sources, unique destinations, every endpoint in range.
    Returns at most ONE violation (the first defect found) so a seeded
    non-bijective permutation maps to exactly one finding."""
    pairs = [tuple(p) for p in perm]
    srcs = [s for s, _ in pairs]
    dsts = [d for _, d in pairs]
    for s, d in pairs:
        if not (0 <= s < axis_size and 0 <= d < axis_size):
            return [Violation(
                "ppermute-bijection", where,
                f"pair ({s}, {d}) outside the axis [0, {axis_size})")]
    if len(set(srcs)) != len(srcs):
        dup = sorted(s for s in set(srcs) if srcs.count(s) > 1)
        return [Violation(
            "ppermute-bijection", where,
            f"duplicate source(s) {dup}: two sends from one device "
            f"collide — perm {tuple(pairs)} is not injective")]
    if len(set(dsts)) != len(dsts):
        dup = sorted(d for d in set(dsts) if dsts.count(d) > 1)
        return [Violation(
            "ppermute-bijection", where,
            f"duplicate destination(s) {dup}: two payloads land on one "
            f"device — perm {tuple(pairs)} is not a bijection")]
    return []


def check_perm_schedule(perm, num_stages: int, virtual_stages: int,
                        where: str = "perm"):
    """The permutation (as a set of pairs) must be the schedule's forward
    hop or its transpose (the backward hop).  Returns at most one
    violation."""
    fwd, bwd = expected_hop_perms(num_stages, virtual_stages)
    got = frozenset(tuple(p) for p in perm)
    if got in (frozenset(fwd), frozenset(bwd)):
        return []
    return [Violation(
        "ppermute-schedule", where,
        f"perm {sorted(got)} matches neither the schedule's forward hop "
        f"{sorted(fwd)} nor its transpose {sorted(bwd)} "
        f"(S={num_stages}, v={virtual_stages})")]


# ---------------------------------------------------------------------------
# Wire payload contract (what a codec is allowed to put on the wire).
# ---------------------------------------------------------------------------


def hop_contract(wire_dtype: str, act_dtype: str = "float32",
                 d_model: int = 0) -> dict:
    """The on-wire contract of one hop under ``wire_dtype`` for an
    activation of HLO/numpy dtype ``act_dtype`` and row width ``d_model``:
    which element types may ride the ppermute, the top-k index dtype, and
    whether the net-loss raw fallback applies."""
    base, frac = autotune._parse_wire(wire_dtype)
    act_hlo = NP_TO_HLO_DTYPE.get(act_dtype, act_dtype)
    act_bytes = _HLO_DTYPE_BYTES.get(act_hlo, 0)
    d = int(d_model)
    block = autotune.wire_block_for(d)
    net_loss = base != "none" and (1.0 + 4.0 / block) >= float(act_bytes)
    kk = max(1, min(d, int(round(frac * d)))) if frac and d else None
    idx_hlo = None
    if frac is not None:
        idx_hlo = "s16" if d <= 32767 else "s32"
    return {
        "wire_dtype": wire_dtype, "base": base, "frac": frac,
        "act_hlo": act_hlo, "act_bytes": act_bytes,
        "payload_hlo": PAYLOAD_HLO_DTYPE.get(base),
        "idx_hlo": idx_hlo, "kk": kk,
        "d_model": d, "block": block, "net_loss": net_loss,
    }


def classify_hop_payload(contract: dict, hlo_dtype: str, dims,
                         where: str = "hop"):
    """Violations for one buffer riding a hop ppermute under
    ``contract`` (built by ``hop_contract``).

    Legitimate buffers: the raw activation ('none' codec, or a declared
    net-loss fallback), the base codec's quantized payload, trailing-dim-1
    f32 scales, and (top-k only) the declared index dtype.  A full-width
    float payload under a quantized codec is the "forged f32 hop" the
    auditor exists to catch.
    """
    dims = tuple(dims)
    c = contract
    if c["base"] == "none":
        if hlo_dtype != c["act_hlo"]:
            return [Violation(
                "wire-payload-dtype", where,
                f"raw hop ships {hlo_dtype}{list(dims)} but the "
                f"activation is {c['act_hlo']} — wire_dtype='none' must "
                "be bit-for-bit the uncoded pipeline")]
        return []
    if hlo_dtype == c["payload_hlo"]:
        return []
    if hlo_dtype in ("s16", "s32"):
        if c["frac"] is None:
            return [Violation(
                "wire-index-dtype", where,
                f"index payload {hlo_dtype}{list(dims)} on a dense "
                f"{c['wire_dtype']!r} hop — only '+topk' codecs ship "
                "indices")]
        if hlo_dtype != c["idx_hlo"]:
            return [Violation(
                "wire-index-dtype", where,
                f"top-k indices are {hlo_dtype} but d_model="
                f"{c['d_model']} declares {c['idx_hlo']} "
                "(wire.topk_index_dtype)")]
        return []
    if hlo_dtype == "f32" and dims and dims[-1] == 1:
        return []     # per-block / per-row scales
    if hlo_dtype == c["act_hlo"] and c["net_loss"]:
        return []     # documented codec_net_loss raw fallback
    return [Violation(
        "wire-payload-dtype", where,
        f"{hlo_dtype}{list(dims)} payload on a {c['wire_dtype']!r} hop — "
        f"declared codec ships {c['payload_hlo']} payloads"
        + ("" if c["frac"] is None else f" + {c['idx_hlo']} indices")
        + " + trailing-dim-1 f32 scales (a full-width float here is a "
        "re-inflated hop that voids the planner byte model)")]


# ---------------------------------------------------------------------------
# Planner byte-model honesty (autotune vs the payload contract).
# ---------------------------------------------------------------------------


def expected_schedule_ticks(k: int, num_stages: int,
                            virtual_stages: int) -> int:
    """One-direction tick count of the interleaved 1F1B schedule,
    re-derived here from the schedule definition (``sigma(m) =
    (m//S)*S*v + m%S``; last entry plus the S*v-tick drain) —
    independent of ``autotune.schedule_ticks`` so drift in the planner's
    copy of the schedule math is detectable."""
    s, v = int(num_stages), int(virtual_stages)
    sigma_last = ((k - 1) // s) * s * v + ((k - 1) % s)
    return sigma_last + s * v


def check_byte_model(wire_dtype: str, direction: str = "fwd", *,
                     act_bytes: float = 4.0, d_model: int = 2560,
                     payload_bytes: float = 1.0, scale_bytes: float = 4.0,
                     index_bytes: float | None = None,
                     rtol: float = 1e-9):
    """Reconcile ``autotune.wire_bytes_per_element(_bwd)`` against the
    wire format's first-principles byte count for one (codec, direction).

    The expectation is derived HERE, independently, from the payload
    contract: dense hop = 1 payload byte/element + 4 scale bytes per
    block; top-k backward hop = ``frac*(1 + idx)`` + 4 bytes per row of
    d.  The ``payload_bytes``/``scale_bytes``/``index_bytes`` knobs exist
    so tests can perturb one constant by 1 and prove the detector fires
    with exactly one classified violation; production calls leave the
    defaults (the real wire format).
    """
    base, frac = autotune._parse_wire(wire_dtype)
    block = autotune.wire_block_for(d_model)
    d = int(d_model)
    where = f"byte-model:{wire_dtype}:{direction}"
    if base == "none":
        want = float(act_bytes)
    else:
        dense = float(payload_bytes) + float(scale_bytes) / block
        if direction == "fwd" or frac is None or dense >= float(act_bytes):
            want = dense
        else:
            idx = index_bytes
            if idx is None:
                idx = 2.0 if d <= 32767 else 4.0
            want = frac * (float(payload_bytes) + idx) \
                + float(scale_bytes) / d
    if direction == "fwd":
        got = autotune.wire_bytes_per_element(wire_dtype, act_bytes, block)
    else:
        got = autotune.wire_bytes_per_element_bwd(wire_dtype, act_bytes,
                                                  block, d)
    if abs(got - want) > rtol * max(abs(got), abs(want), 1e-12):
        return [Violation(
            "wire-bytes-model", where,
            f"autotune bills {got:.6g} B/element but the wire format "
            f"costs {want:.6g} (act_bytes={act_bytes}, block={block}, "
            f"d_model={d}) — codec and planner drifted apart")]
    return []


def audit_byte_model(*, act_bytes: float = 4.0, d_model: int = 2560,
                     wires=autotune.WIRE_AUTO, **knobs):
    """Byte-model reconciliation over every codec x direction."""
    out = []
    for w in wires:
        for direction in ("fwd", "bwd"):
            out += check_byte_model(w, direction, act_bytes=act_bytes,
                                    d_model=d_model, **knobs)
    return out


def audit_record_honesty(record: dict, *, rtol: float = 1e-6, **knobs):
    """Planner honesty on a dry-run record (e.g. the checked-in
    ``tests/fixtures/roofline_smoke.json``): (1) re-billing the extracted
    uncompressed hop through the byte model must reproduce the record's
    measured per-chip collective-permute bytes (drift in the tick/sigma
    schedule math or the extraction inversion fires here), and (2) the
    byte model itself must match the payload contract at the record's
    act_bytes / block / d_model (``audit_byte_model``).

    Returns ``(violations, stats)``.
    """
    rl = record.get("roofline", record)
    hints = record.get("planner_hints", {})
    inp = autotune.plan_inputs_from_record(record)
    k0 = int(record.get("pipeline_k", 0) or 0)
    v0 = int(record.get("pipeline_v", 1) or 1)
    s0 = int(hints.get("num_stages", inp.num_stages))
    pp = float(rl.get("coll_by_kind", {}).get("collective-permute", 0.0))
    violations = []
    stats = {"k0": k0, "v0": v0, "num_stages": s0,
             "act_hop_bytes": inp.act_hop_bytes,
             "measured_pp_bytes": pp}
    if k0 and pp > 0:
        ticks0 = autotune.schedule_ticks(k0, s0, v0)
        want_ticks = expected_schedule_ticks(k0, s0, v0)
        if ticks0 != want_ticks:
            violations.append(Violation(
                "wire-bytes", f"record:{record.get('arch', '?')}",
                f"autotune.schedule_ticks bills {ticks0} ticks but the "
                f"1F1B schedule definition gives {want_ticks} "
                f"(k={k0}, S={s0}, v={v0}) — the planner's schedule "
                "math drifted from the tick loop's"))
        rec_wire = record.get("wire_dtype", "none")
        mean_scale = 0.5 * (
            autotune.wire_link_scale(rec_wire, inp.act_bytes,
                                     inp.wire_block)
            + autotune.wire_link_scale_bwd(rec_wire, inp.act_bytes,
                                           inp.wire_block, inp.d_model))
        rebilled = 2.0 * ticks0 / k0 * inp.act_hop_bytes * mean_scale
        stats.update(ticks0=ticks0, rebilled_pp_bytes=rebilled)
        if abs(rebilled - pp) > rtol * max(pp, 1e-12):
            violations.append(Violation(
                "wire-bytes", f"record:{record.get('arch', '?')}",
                f"re-billing the extracted hop gives {rebilled:.6g} "
                f"collective-permute B/chip vs the record's {pp:.6g} — "
                "the schedule/extraction math no longer round-trips"))
    violations += audit_byte_model(act_bytes=inp.act_bytes,
                                   d_model=inp.d_model or 0, **knobs)
    return violations, stats


# ---------------------------------------------------------------------------
# jaxpr-level audit (device-free, via an abstract mesh).
# ---------------------------------------------------------------------------

# pod-axis collectives that are NOT the pipeline hop: any of these inside
# the shard_map-over-pod region means a stage is secretly gathering or
# reducing across the stage boundary.
_LEAK_PRIMS = ("psum", "psum2", "all_gather", "all_to_all",
               "reduce_scatter", "pmax", "pmin", "allreduce")


def _sub_jaxprs(v):
    if hasattr(v, "eqns"):
        return [v]
    if hasattr(v, "jaxpr"):
        return [v.jaxpr]
    if isinstance(v, (list, tuple)):
        return [s for item in v for s in _sub_jaxprs(item)]
    return []


_LOOP_PRIMS = ("scan", "while", "while_loop")


def iter_jaxpr_eqns(jaxpr, in_loop: bool = False):
    """Yield ``(eqn, in_loop)`` for every eqn of a (Closed)Jaxpr
    recursively, sub-jaxprs included (scan/while bodies, shard_map
    regions, custom_vjp calls).  ``in_loop`` is True once the walk has
    descended through a scan/while — the jaxpr-level analogue of the HLO
    audit's while-reachable scoping: collectives at entry level (e.g.
    the shard_map transpose's replicated-param grad psum) are
    legitimate; the same collective inside the tick loop is a leak."""
    for sub in _sub_jaxprs(jaxpr):
        for eqn in sub.eqns:
            yield eqn, in_loop
            inner = in_loop or eqn.primitive.name in _LOOP_PRIMS
            for v in eqn.params.values():
                yield from iter_jaxpr_eqns(v, inner)


def _eqn_axes(eqn):
    ax = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if isinstance(ax, str):
        return (ax,)
    try:
        return tuple(ax)
    except TypeError:
        return (ax,)


def audit_jaxpr(closed_jaxpr, *, num_stages: int, virtual_stages: int,
                wire_dtype: str, d_model: int,
                act_dtype: str = "float32", axis: str = "pod"):
    """Audit one traced pipeline loss/grad jaxpr.  Returns
    ``(violations, stats)``."""
    fwd, bwd = expected_hop_perms(num_stages, virtual_stages)
    contract = hop_contract(wire_dtype, act_dtype, d_model)
    violations = []
    n_pp = 0
    n_eqn = 0
    dirs_seen = set()
    payload_dirs = set()
    idx_dirs = set()
    for eqn, in_loop in iter_jaxpr_eqns(closed_jaxpr):
        n_eqn += 1
        name = eqn.primitive.name
        if name == "ppermute":
            if axis not in _eqn_axes(eqn):
                continue
            n_pp += 1
            perm = tuple(tuple(p) for p in eqn.params["perm"])
            aval = eqn.invars[0].aval
            dt = NP_TO_HLO_DTYPE.get(str(aval.dtype), str(aval.dtype))
            where = f"jaxpr:ppermute#{n_pp}:{dt}{list(aval.shape)}"
            violations += check_perm_bijection(perm, num_stages, where)
            violations += check_perm_schedule(perm, num_stages,
                                              virtual_stages, where)
            # direction by pair set; for S=2 cyclic schedules fwd and its
            # transpose are the SAME set — such a hop satisfies both
            got = frozenset(perm)
            dirs = tuple(d for d, p in (("fwd", fwd), ("bwd", bwd))
                         if got == frozenset(p)) or ("?",)
            dirs_seen.update(dirs)
            pv = classify_hop_payload(contract, dt, aval.shape, where)
            violations += pv
            if not pv and dt == contract["payload_hlo"]:
                payload_dirs.update(dirs)
            if not pv and dt in ("s16", "s32"):
                idx_dirs.add("bwd")  # only the gradient hop ships indices
        elif any(name.startswith(p) for p in _LEAK_PRIMS):
            if in_loop and axis in _eqn_axes(eqn):
                violations.append(Violation(
                    "sharding-leak", f"jaxpr:{name}",
                    f"{name} over the {axis!r} axis inside the tick "
                    "loop — only the hop ppermute may cross the stage "
                    "boundary (entry-level replicated-grad reductions "
                    "are fine)"))
    # completeness: every direction of the schedule must actually hop,
    # and a coded hop must actually put coded payloads on the wire
    if num_stages > 1:
        for direction, perm in (("fwd", fwd), ("bwd", bwd)):
            if direction not in dirs_seen:
                violations.append(Violation(
                    "ppermute-schedule", f"jaxpr:{direction}",
                    f"no ppermute with the schedule's {direction} hop "
                    f"{sorted(frozenset(perm))} was traced — the "
                    f"{direction} hop is missing"))
        if contract["base"] != "none" and not contract["net_loss"]:
            for direction in ("fwd", "bwd"):
                if direction not in payload_dirs:
                    violations.append(Violation(
                        "wire-payload-dtype", f"jaxpr:{direction}",
                        f"declared codec {wire_dtype!r} but no "
                        f"{contract['payload_hlo']} payload rides the "
                        f"{direction} hop — the codec was compiled away"))
            if contract["frac"] is not None and "bwd" not in idx_dirs:
                violations.append(Violation(
                    "wire-index-dtype", "jaxpr:bwd",
                    f"declared top-k codec {wire_dtype!r} but no "
                    f"{contract['idx_hlo']} index payload rides the "
                    "backward hop"))
    stats = {"n_eqns": n_eqn, "n_ppermute": n_pp,
             "directions": sorted(dirs_seen)}
    return violations, stats


def audit_custom_vjp_pair(fwd_fn, bwd_fn, primal_avals, *,
                          where: str = "custom_vjp",
                          ef_dtype: str = "float32"):
    """Residual-dtype consistency of a custom_vjp (fwd, bwd) pair under
    abstract evaluation: residuals the fwd rule saves must come back from
    the bwd rule with the same shape/dtype (the EF buffer contract), and
    the cotangent returned for the primal must keep the primal's dtype
    (the straight-through wire transpose contract).

    ``fwd_fn(*primals) -> (out, res)``; ``bwd_fn(res, g) -> (gx, ...)``
    with ``g`` shaped like ``out``.  Returns a violation list.
    """
    import jax

    violations = []
    out, res = jax.eval_shape(fwd_fn, *primal_avals)
    grads = jax.eval_shape(bwd_fn, res, out)
    grads = tuple(grads) if isinstance(grads, (tuple, list)) else (grads,)
    x = primal_avals[0]
    gx = grads[0]
    if str(gx.dtype) != str(x.dtype) or tuple(gx.shape) != tuple(x.shape):
        violations.append(Violation(
            "vjp-residual-dtype", where,
            f"bwd returns cotangent {gx.dtype}{list(gx.shape)} for primal "
            f"{x.dtype}{list(x.shape)} — the straight-through transpose "
            "must keep the primal's aval"))
    if res is not None:
        res_leaves = jax.tree_util.tree_leaves(res)
        new_leaves = jax.tree_util.tree_leaves(grads[1:])
        for i, r in enumerate(res_leaves):
            if str(r.dtype) != ef_dtype:
                violations.append(Violation(
                    "vjp-residual-dtype", where,
                    f"fwd residual #{i} is {r.dtype} — the error-feedback "
                    f"state contract is {ef_dtype} (wire.coded_ppermute_ef)"))
        for i, (r, n) in enumerate(zip(res_leaves, new_leaves)):
            if str(n.dtype) != str(r.dtype) \
                    or tuple(n.shape) != tuple(r.shape):
                violations.append(Violation(
                    "vjp-residual-dtype", where,
                    f"bwd returns residual #{i} as {n.dtype}{list(n.shape)}"
                    f" but fwd saved {r.dtype}{list(r.shape)} — the EF "
                    "buffer would change aval across steps"))
    return violations


def audit_wire_custom_vjp(wire_dtype: str, *, d_model: int = 64,
                          act_dtype: str = "float32"):
    """Apply ``audit_custom_vjp_pair`` to the live wire codec's
    custom_vjp rules (identity permutation on a 1-wide abstract pod
    axis — dtype/shape flow only, no devices)."""
    import jax

    from repro.parallel import compat, wire
    from repro.parallel.compat import PartitionSpec as P

    base, frac = autotune._parse_wire(wire_dtype)
    if base == "none":
        return []
    mesh = compat.abstract_mesh((1,), ("pod",))
    perm = ((0, 0),)
    x = jax.ShapeDtypeStruct((2, 4, d_model), act_dtype)
    where = f"wire:{wire_dtype}"
    if frac is None:
        def fwd(xx):
            return wire._coded_fwd(wire_dtype, "pod", perm, xx)

        def bwd(res, g):
            return wire._coded_bwd(wire_dtype, "pod", perm, res, g)
        sm_fwd = compat.shard_map(fwd, mesh, in_specs=(P(),),
                                  out_specs=(P(), P()))

        def sm_bwd(res, g):
            return compat.shard_map(
                lambda gg: bwd(res, gg), mesh, in_specs=(P(),),
                out_specs=(P(),))(g)
        # dense codec: no residual state (res is None) — wrap so the
        # shard_map out_specs stay a plain pytree
        import jax as _jax
        out, _ = _jax.eval_shape(sm_fwd, x)
        grads = _jax.eval_shape(lambda g: sm_bwd(None, g), out)
        violations = []
        gx = grads[0]
        if str(gx.dtype) != str(x.dtype) \
                or tuple(gx.shape) != tuple(x.shape):
            violations.append(Violation(
                "vjp-residual-dtype", where,
                f"bwd cotangent {gx.dtype}{list(gx.shape)} != primal "
                f"{x.dtype}{list(x.shape)}"))
        return violations
    ef = jax.ShapeDtypeStruct((2, 4, d_model), "float32")

    def fwd(xx, ee):
        return wire._coded_ef_fwd(wire_dtype, "pod", perm, xx, ee)

    def bwd(res, g):
        return wire._coded_ef_bwd(wire_dtype, "pod", perm, res, g)
    sm_fwd = compat.shard_map(fwd, mesh, in_specs=(P(), P()),
                              out_specs=(P(), P()))

    def sm_bwd(res, g):
        return compat.shard_map(bwd, mesh, in_specs=(P(), P()),
                                out_specs=(P(), P()))(res, g)
    return audit_custom_vjp_pair(
        lambda xx, ee: sm_fwd(xx, ee),
        sm_bwd, (x, ef), where=where)


# ---------------------------------------------------------------------------
# HLO-level audit (compiled text; scoped to while-reachable computations).
# ---------------------------------------------------------------------------


def audit_hlo_text(text: str, *, pod_size: int, num_stages: int,
                   virtual_stages: int, wire_dtype: str, d_model: int,
                   act_dtype: str = "float32", hop_elems: int | None = None,
                   bytes_rtol: float = 0.01,
                   checks=("perm", "payload", "leak", "bytes")):
    """Audit one compiled module's text.  Returns ``(violations, stats)``.

    Scope: computations reachable through a ``while`` (the tick loops) —
    entry-level collectives (replicated-grad reductions, GSPMD input
    reshards) are legitimate and ignored.  ``pod_size`` is devices per
    pod (= total devices / num_stages on our pod-major meshes);
    ``hop_elems`` is the PER-DEVICE element count of one hop payload
    (micro-batch-shard x seq x d_model), enabling the byte-honesty
    reconciliation against ``autotune.wire_bytes_per_element(_bwd)``.
    """
    comps = hlo_costs.parse_hlo(text)
    in_loop = hlo_costs.while_reachable(comps)
    mult = hlo_costs.computation_multipliers(comps)
    contract = hop_contract(wire_dtype, act_dtype, d_model)
    fwd, bwd = expected_hop_perms(num_stages, virtual_stages)
    fwd_bwd = frozenset(fwd) | frozenset(bwd)
    ticks = autotune.schedule_ticks(1, num_stages, virtual_stages)  # dummy
    violations = []
    n_cp = 0
    n_local_cp = 0
    group_bytes: dict = {}     # comp -> [per-tick cross-pod hop bytes]
    group_kinds: dict = {}     # comp -> set of payload dtypes seen
    for name in in_loop:
        for ins in comps[name]:
            op = ins.opcode
            is_cp = op in ("collective-permute", "collective-permute-start")
            if not is_cp:
                for kind in hlo_costs.COLLECTIVES:
                    if kind == "collective-permute":
                        continue
                    if op in (kind, kind + "-start") and "leak" in checks \
                            and hlo_costs._crosses_pod(ins.rest, pod_size):
                        violations.append(Violation(
                            "sharding-leak", f"hlo:{name}:{ins.name}",
                            f"cross-pod {kind} {ins.rtype} inside the "
                            "tick loop — stage-internal collectives must "
                            "stay within the pod; only the hop ppermute "
                            "crosses the boundary"))
                continue
            pairs = hlo_costs.source_target_pairs(ins.rest)
            cross = [(s, t) for s, t in pairs
                     if s // pod_size != t // pod_size]
            if not cross:
                n_local_cp += 1    # within-pod reshard, not a hop
                continue
            n_cp += 1
            shape = hlo_costs.result_shape(ins.rtype)
            dt, dims = shape if shape else ("?", ())
            where = f"hlo:{name}:{ins.name}:{dt}{list(dims)}"
            if "perm" in checks:
                violations += check_perm_bijection(
                    pairs, pod_size * num_stages, where)
                lifted = set()
                bad_lift = False
                for s, t in cross:
                    if s % pod_size != t % pod_size:
                        bad_lift = True
                    lifted.add((s // pod_size, t // pod_size))
                if bad_lift:
                    violations.append(Violation(
                        "ppermute-schedule", where,
                        f"hop pairs {cross} do not preserve the in-pod "
                        "rank — the device permutation is not the pod "
                        "hop lifted over the pod"))
                elif not lifted <= fwd_bwd:
                    violations.append(Violation(
                        "ppermute-schedule", where,
                        f"pod-lifted pairs {sorted(lifted)} not within "
                        f"the schedule's hops {sorted(fwd_bwd)} "
                        f"(S={num_stages}, v={virtual_stages})"))
            if "payload" in checks:
                violations += classify_hop_payload(contract, dt, dims,
                                                   where)
            nb = _HLO_DTYPE_BYTES.get(dt, 0)
            for d_ in dims:
                nb *= d_
            group_bytes.setdefault(name, []).append(nb)
            group_kinds.setdefault(name, set()).add(dt)
    stats = {"n_hop_cp": n_cp, "n_local_cp": n_local_cp,
             "loop_comps_with_hops": sorted(group_bytes)}
    if "bytes" in checks and hop_elems and num_stages > 1:
        block = autotune.wire_block_for(d_model)
        w_f = autotune.wire_bytes_per_element(
            wire_dtype, contract["act_bytes"], block)
        w_b = autotune.wire_bytes_per_element_bwd(
            wire_dtype, contract["act_bytes"], block, d_model)
        obs = sum(sum(v) for v in group_bytes.values())
        want = hop_elems * (w_f + w_b)
        stats.update(hop_bytes_per_tick=obs,
                     billed_bytes_per_tick=want,
                     bytes_per_element=obs / hop_elems if hop_elems else 0,
                     billed_per_element=w_f + w_b)
        if abs(obs - want) > bytes_rtol * max(want, 1e-12):
            violations.append(Violation(
                "wire-bytes", "hlo:bytes",
                f"compiled hop ships {obs} B/tick/device but the planner "
                f"bills {want:.6g} (w_fwd={w_f:.4g} + w_bwd={w_b:.4g} "
                f"B/element x {hop_elems} elements) — billed bytes != "
                "compiled bytes"))
        del ticks
    return violations, stats


# ---------------------------------------------------------------------------
# Fixture cells: the re-planner's reachable cell set.
# ---------------------------------------------------------------------------

# the fixture cell (mirrors the tier-1 tiny config; float32 so the
# CPU-backend float-normalization upcast cannot blur byte accounting)
_CELL = dict(num_stages=2, microbatches=3, batch=6, seq=16,
             num_layers=4,
             mesh_shape=(2, 2, 2), axis_names=("pod", "data", "model"))

# The audit grid is no longer hand-picked: it is the ONLINE RE-PLANNER's
# reachable (wire, v) cell set for the fixture cell — every lowering a
# ``training.replan.Replanner`` over the default ``WIRE_AUTO``
# candidates can switch into mid-run must stay green here, or a plan
# switch could land on a cell the auditor never saw.  (k moves shapes,
# not the lowering grammar, so cells collapse over k; the fixture's
# ragged k=3 over batch=6 exercises padding.)
from repro.training.replan import reachable_cells as _reachable_cells

AUDIT_CELLS = tuple(_reachable_cells(num_stages=_CELL["num_stages"],
                                     num_layers=_CELL["num_layers"],
                                     v_cap=4))
AUDIT_WIRES = tuple(dict.fromkeys(w for w, _v in AUDIT_CELLS))
AUDIT_VS = tuple(sorted(dict.fromkeys(v for _w, v in AUDIT_CELLS)))


def _cell_model():
    from repro.models import LM, LMConfig
    cfg = LMConfig(name="audit", num_layers=_CELL["num_layers"],
                   d_model=64, n_heads=4,
                   n_kv=2, d_ff=128, vocab=256, dtype="float32")
    return LM(cfg)


def _cell_fns(wire: str, v: int, mesh):
    """(grad_fn, example_args, meta) for one audit cell on ``mesh``
    (abstract for jaxpr tracing, concrete for compilation)."""
    import jax

    from repro.data import lm_batch_for
    from repro.parallel.pipeline import (PipelineSpec, make_pipelined_loss,
                                         wire_ef_zeros)
    model = _cell_model()
    cfg = model.cfg
    spec = PipelineSpec(num_stages=_CELL["num_stages"],
                        microbatches=_CELL["microbatches"],
                        virtual_stages=v, wire_dtype=wire)
    params = model.init(jax.random.key(0))
    batch = lm_batch_for(cfg, _CELL["batch"], _CELL["seq"])
    loss = make_pipelined_loss(model, spec, mesh=mesh)
    n_data = _CELL["mesh_shape"][1]
    mb = _CELL["batch"] // _CELL["microbatches"]
    mb_local = mb // n_data if mb % n_data == 0 else mb
    meta = {
        "wire": spec.wire_dtype, "v": v,
        "num_stages": spec.num_stages, "k": spec.microbatches,
        "d_model": cfg.d_model, "act_dtype": cfg.dtype,
        "pod_size": (_CELL["mesh_shape"][1] * _CELL["mesh_shape"][2]),
        "hop_elems": mb_local * _CELL["seq"] * cfg.d_model,
    }
    if loss.needs_wire_ef:
        ef = wire_ef_zeros(cfg, spec, _CELL["batch"], _CELL["seq"])

        def fn(p, e):
            return loss(p, batch, e)[0]
        grad_fn = jax.value_and_grad(fn, argnums=(0, 1))
        return grad_fn, (params, ef), meta

    def fn(p):
        return loss(p, batch)[0]
    return jax.value_and_grad(fn), (params,), meta


def audit_cells(level: str = "jaxpr", wires=None, vs=None,
                bytes_rtol: float = 0.01, cells=None):
    """Run the auditor over the re-planner's reachable cell set.

    By default the grid is ``AUDIT_CELLS`` — the (wire, v) set a
    ``training.replan.Replanner`` can switch into on the fixture cell.
    ``wires``/``vs`` restrict to a sub-product (their cross product);
    ``cells`` pins an explicit ``[(wire, v), ...]`` list and wins over
    both.  ``level``:

      * ``'jaxpr'`` — abstract-mesh tracing, zero devices needed;
      * ``'hlo'`` — compiles each cell (requires
        ``mesh_shape`` devices, e.g. XLA_FLAGS
        --xla_force_host_platform_device_count=8) and audits the
        optimized module text, including byte honesty.

    Returns ``(violations, cells)`` where ``cells`` is a list of per-cell
    stat dicts keyed by ``wire/v``.
    """
    import jax

    from repro.parallel import compat

    if cells is None:
        cells = [(w, v) for w in (AUDIT_WIRES if wires is None else wires)
                 for v in (AUDIT_VS if vs is None else vs)]
    else:
        cells = list(cells)
    violations = []
    out_cells = []
    for wire, v in cells:
        key = f"{wire}/v{v}"
        if level == "jaxpr":
            mesh = compat.abstract_mesh(_CELL["mesh_shape"],
                                        _CELL["axis_names"])
            grad_fn, args, meta = _cell_fns(wire, v, mesh)
            jaxpr = jax.make_jaxpr(grad_fn)(*args)
            vio, stats = audit_jaxpr(
                jaxpr, num_stages=meta["num_stages"],
                virtual_stages=v, wire_dtype=meta["wire"],
                d_model=meta["d_model"], act_dtype=meta["act_dtype"])
        elif level == "hlo":
            ndev = 1
            for n in _CELL["mesh_shape"]:
                ndev *= n
            if len(jax.devices()) < ndev:
                raise RuntimeError(
                    f"HLO-level audit needs {ndev} devices (set "
                    "XLA_FLAGS=--xla_force_host_platform_device_count="
                    f"{ndev} before importing jax; the CLI does this)")
            mesh = compat.make_mesh(_CELL["mesh_shape"],
                                    _CELL["axis_names"])
            grad_fn, args, meta = _cell_fns(wire, v, mesh)
            text = jax.jit(grad_fn).lower(*args).compile().as_text()
            vio, stats = audit_hlo_text(
                text, pod_size=meta["pod_size"],
                num_stages=meta["num_stages"], virtual_stages=v,
                wire_dtype=meta["wire"], d_model=meta["d_model"],
                act_dtype=meta["act_dtype"],
                hop_elems=meta["hop_elems"], bytes_rtol=bytes_rtol)
        else:
            raise ValueError(f"unknown audit level {level!r}")
        vio = [dataclasses.replace(x, where=f"{key}:{x.where}")
               for x in vio]
        violations += vio
        out_cells.append({"cell": key, "level": level,
                          "violations": len(vio), "stats": stats})
    # the custom_vjp residual contract is cell-independent — audit once
    # per coded grammar
    for wire in dict.fromkeys(w for w, _v in cells):
        if autotune._parse_wire(wire)[0] != "none":
            vio = audit_wire_custom_vjp(wire)
            violations += vio
            out_cells.append({"cell": f"vjp:{wire}", "level": "jaxpr",
                              "violations": len(vio), "stats": {}})
    return violations, out_cells


# ---------------------------------------------------------------------------
# Report / diff / CLI.
# ---------------------------------------------------------------------------

ROOFLINE_FIXTURE = os.path.join(
    os.path.dirname(__file__), "..", "..", "..", "tests", "fixtures",
    "roofline_smoke.json")


def build_report(level: str = "jaxpr", lint_paths=None,
                 record_path: str | None = None) -> dict:
    """Run every layer the ``level`` admits and assemble the JSON
    violation report the CI job uploads.  The diffable core is the
    ``ok``/``by_class``/``cells`` keys: eqn counts live in per-cell
    stats, which ``diff_report`` ignores."""
    from repro.analysis import lint as lint_pack

    violations = []
    levels = ("jaxpr",) if level == "jaxpr" else ("jaxpr", "hlo")
    cells = []
    for lv in levels:
        vio, cl = audit_cells(level=lv)
        if lv != levels[0]:       # vjp cells repeat per level — keep one
            cl = [c for c in cl if not c["cell"].startswith("vjp:")]
            vio = [v for v in vio if not v.where.startswith("wire:")]
        violations += vio
        cells += cl
    rec_path = record_path or ROOFLINE_FIXTURE
    rec_stats: dict = {}
    if os.path.exists(rec_path):
        with open(rec_path) as f:
            record = json.load(f)
        vio, rec_stats = audit_record_honesty(record)
        violations += vio
    lint_violations = lint_pack.lint_paths(lint_paths or
                                           [_default_lint_root()])
    violations += [Violation("lint", f"{v.path}:{v.line}",
                             f"{v.rule}: {v.detail}")
                   for v in lint_violations]
    return {
        "schema": 1,
        "level": level,
        "ok": not violations,
        "by_class": by_class(violations),
        "cells": sorted(f"{c['level']}:{c['cell']}" for c in cells),
        "violations": [v.to_dict() for v in violations],
        "cell_stats": cells,
        "record_honesty": rec_stats,
    }


def _default_lint_root() -> str:
    return os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))


def diff_report(new: dict, baseline: dict):
    """Leg-independent comparison of a fresh report against the committed
    green baseline (``benchmarks/STATICCHECK_baseline.json``).  Returns a
    list of mismatch strings (empty = clean)."""
    fails = []
    if bool(new.get("ok")) != bool(baseline.get("ok")):
        fails.append(f"ok: {new.get('ok')} != baseline {baseline.get('ok')}")
    if dict(new.get("by_class", {})) != dict(baseline.get("by_class", {})):
        fails.append(f"by_class: {new.get('by_class')} != baseline "
                     f"{baseline.get('by_class')}")
    nc, bc = list(new.get("cells", [])), list(baseline.get("cells", []))
    if sorted(nc) != sorted(bc):
        fails.append(f"cells: {sorted(nc)} != baseline {sorted(bc)}")
    return fails


# ---------------------------------------------------------------------------
# Seeded-violation corpus: prove every detector fires (--selftest).
# ---------------------------------------------------------------------------

CORPUS_DIR = os.path.join(os.path.dirname(ROOFLINE_FIXTURE),
                          "staticcheck_corpus")


def selftest(corpus_dir: str | None = None) -> dict:
    """Run every detector against its seeded violation and assert it
    fires with the right class — the auditor auditing itself.  Returns
    ``{detector: fired_class}``; raises AssertionError on any silent
    detector."""
    from repro.analysis import lint as lint_pack

    corpus = corpus_dir or CORPUS_DIR
    fired: dict = {}

    def expect(name, violations, cls, n=1):
        got = [v for v in violations if v.cls == cls]
        assert len(got) == n and len(violations) == n, (
            f"selftest {name}: expected exactly {n} {cls!r} violation, "
            f"got {[(v.cls, v.detail) for v in violations]}")
        fired[name] = cls

    # 1. non-bijective permutation (duplicate destination)
    expect("perm-bijection",
           check_perm_bijection(((0, 1), (1, 1)), 2), "ppermute-bijection")
    # 2. bijective but off-schedule permutation
    expect("perm-schedule",
           check_perm_schedule(((0, 1), (1, 0)), 4, 1), "ppermute-schedule")
    # 3. forged f32 payload on a declared-int8 hop
    c = hop_contract("int8", "float32", 64)
    expect("payload-forged-f32",
           classify_hop_payload(c, "f32", (1, 16, 64)), "wire-payload-dtype")
    # 4. int32 indices where d_model declares int16
    ct = hop_contract("int8+topk0.25", "float32", 64)
    expect("index-dtype",
           classify_hop_payload(ct, "s32", (1, 16, 16)), "wire-index-dtype")
    # 5. planner byte-model constant perturbed by 1
    expect("byte-model-perturbed",
           check_byte_model("int8", "fwd", payload_bytes=2.0),
           "wire-bytes-model")
    # 6. broken custom_vjp pair (bwd residual dtype drifts to bf16)
    import jax

    def bad_fwd(x):
        return x, jax.ShapeDtypeStruct(x.shape, "float32")

    def bad_bwd(res, g):
        import jax.numpy as jnp
        return (g, jnp.zeros(res.shape, "bfloat16"))
    expect("vjp-residual",
           audit_custom_vjp_pair(
               bad_fwd, bad_bwd,
               (jax.ShapeDtypeStruct((2, 8), "float32"),)),
           "vjp-residual-dtype")
    # 7-9. seeded HLO corpus files, one defect each
    hlo_cases = {
        "hlo-forged-f32-hop": ("hlo_forged_f32_hop.txt",
                               "wire-payload-dtype", ("payload",), "int8"),
        "hlo-sharding-leak": ("hlo_sharding_leak.txt",
                              "sharding-leak", ("leak",), "none"),
        "hlo-nonbijective": ("hlo_nonbijective.txt",
                             "ppermute-bijection", ("perm",), "none"),
    }
    for name, (fname, cls, checks, wire) in hlo_cases.items():
        path = os.path.join(corpus, fname)
        with open(path) as f:
            text = f.read()
        vio, _ = audit_hlo_text(
            text, pod_size=4, num_stages=2, virtual_stages=1,
            wire_dtype=wire, d_model=64, checks=checks)
        expect(name, vio, cls)
    # 10. lint rule pack on the seeded-bad corpus module
    bad_py = os.path.join(corpus, "lint_bad.py")
    lv = lint_pack.lint_paths([bad_py])
    got_rules = sorted({v.rule for v in lv})
    assert got_rules == sorted(lint_pack.RULES), (
        f"selftest lint: rules fired {got_rules} != all rules "
        f"{sorted(lint_pack.RULES)}")
    fired["lint-rules"] = ",".join(got_rules)
    return fired


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.staticcheck",
        description="Pipeline invariant auditor (docs/staticcheck.md)")
    ap.add_argument("--level", choices=("jaxpr", "full"), default="jaxpr",
                    help="'jaxpr' = device-free trace audit; 'full' adds "
                         "the compiled-HLO audit (forces host devices)")
    ap.add_argument("--lint", nargs="*", metavar="PATH",
                    help="run ONLY the AST lint pack over PATHs "
                         "(default: src/repro)")
    ap.add_argument("--selftest", action="store_true",
                    help="run the seeded-violation corpus; every detector "
                         "must fire with its class")
    ap.add_argument("--report", default=None,
                    help="write the JSON violation report here")
    ap.add_argument("--diff", default=None,
                    help="compare the report against this committed "
                         "baseline (benchmarks/STATICCHECK_baseline.json)")
    ap.add_argument("--record", default=None,
                    help="dry-run record for the planner-honesty check "
                         "(default: tests/fixtures/roofline_smoke.json)")
    args = ap.parse_args(argv)

    if args.selftest:
        fired = selftest()
        for name, cls in sorted(fired.items()):
            print(f"  {name:24s} -> {cls}")
        print(f"selftest OK: {len(fired)} detectors fired")
        return 0

    if args.lint is not None:
        from repro.analysis import lint as lint_pack
        paths = args.lint or [_default_lint_root()]
        violations = lint_pack.lint_paths(paths)
        for v in violations:
            print(f"{v.path}:{v.line}: {v.rule}: {v.detail}")
        print(f"{len(violations)} lint finding(s) in {paths}")
        return 1 if violations else 0

    if args.level == "full" and "jax" not in sys.modules:
        # the HLO audit compiles the 8-device fixture mesh on CPU; the
        # flag must be set before the first jax import
        ndev = 1
        for n in _CELL["mesh_shape"]:
            ndev *= n
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={ndev}"
            ).strip()

    report = build_report(level=args.level, record_path=args.record)
    if args.report:
        with open(args.report, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
        print(f"wrote {args.report}")
    for v in report["violations"]:
        print(f"VIOLATION [{v['class']}] {v['where']}: {v['detail']}")
    print(f"staticcheck level={report['level']}: "
          f"{len(report['cells'])} cells, "
          f"{len(report['violations'])} violation(s)")
    rc = 0 if report["ok"] else 1
    if args.diff:
        with open(args.diff) as f:
            baseline = json.load(f)
        fails = diff_report(report, baseline)
        for fmsg in fails:
            print(f"DIFF vs {args.diff}: {fmsg}")
        if fails:
            rc = rc or 2
        else:
            print(f"diff vs {args.diff}: clean")
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
