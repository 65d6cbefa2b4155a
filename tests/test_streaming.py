"""Async multi-client streaming runtime (repro.runtime).

Four lanes:

* jax-free protocol tests — frame round-trip over every codec grammar
  (incl. the net-loss raw fallback at a degenerate block), header
  validation, billed-vs-measured byte math, gradient error feedback.
* host-vs-device codec parity — the numpy ``host_*`` entry points in
  ``parallel/wire.py`` against the jnp kernels (bit-exact for int8 and
  top-k; fp8 bounded by one quantization step, XLA:CPU's f32->f8
  convert rounds near-ties differently from ml_dtypes' RTNE).
* component tests on a real loopback socket — bounded-inbox
  backpressure, ragged-arrival order independence, wire honesty
  (measured socket payload bytes == ``autotune.wire_bytes_per_element``
  /``_bwd`` billing at 1% rtol) for none / int8 / fp8 / int8+topk0.25.
* slow lane — 4 UE clients x >= 20 steps over loopback matching joint
  full-batch training to tolerance (equal shards + elementwise AdamW
  make the streamed trajectory exact up to f32 reduction order; the
  in-process pipeline path equals that same joint step by
  tests/test_pipeline.py), and the re-planner AC: ``LinkEstimator``
  hints come from MEASURED socket hops and track a mid-run
  ``LinkShaper.set_rate`` change — no ``BandwidthTrace`` script in the
  loop.
"""
import asyncio
import json

import numpy as np
import pytest

from repro.runtime import protocol
from repro.runtime.qos import QoSMonitor

CODECS = ["none", "int8", "fp8", "int8+topk0.25"]

#: Each loopback-socket scenario runs under its own deadline, so a hang on
#: the socket fails that one test instead of stalling the whole run.
SOCKET_TIMEOUT_S = 240


def run_bounded(coro, timeout_s: float = SOCKET_TIMEOUT_S):
    return asyncio.run(asyncio.wait_for(coro, timeout_s))


def _tiny_cfg(d_model=32, vocab=64, num_layers=4):
    from repro.models import LMConfig
    return LMConfig(name="t", num_layers=num_layers, d_model=d_model,
                    n_heads=4, n_kv=2, d_ff=64, vocab=vocab,
                    dtype="float32")


# ---------------------------------------------------------------------------
# Protocol: frame round-trip + validation (numpy only)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("wire", CODECS)
def test_act_frame_round_trip(wire):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((4, 16, 32)).astype(np.float32)
    arrays, meta = protocol.encode_act_payload(x, wire)
    arrays["labels"] = rng.integers(0, 64, (4, 16)).astype(np.int32)
    buf = protocol.pack_frame(protocol.ACT, 3, 7, meta=meta, arrays=arrays)
    frame = protocol.unpack_frame(buf[4:], wire_nbytes=len(buf))
    assert (frame.ftype, frame.client, frame.step) == (protocol.ACT, 3, 7)
    assert frame.meta["codec"] == wire
    np.testing.assert_array_equal(frame.arrays["labels"],
                                  arrays["labels"])
    out = protocol.decode_act_payload(frame)
    assert out.dtype == x.dtype and out.shape == x.shape
    if wire == "none":
        np.testing.assert_array_equal(out, x)
    else:
        # dense 8-bit quantization: reconstruction within one quantizer
        # step of the per-block absmax (int8: amax/127; fp8-e4m3 has a
        # 3-bit mantissa, so its step near the clip point is ~amax/16)
        amax = float(np.max(np.abs(x)))
        tol = amax / 100 if wire.startswith("int8") else amax / 14
        assert float(np.max(np.abs(out - x))) < tol
    # payload vs aux split: labels are never billed codec bytes
    assert frame.aux_nbytes == arrays["labels"].nbytes
    assert frame.payload_nbytes == sum(
        a.nbytes for n, a in arrays.items() if n != "labels")


@pytest.mark.parametrize("wire", ["int8+topk0.25", "fp8+topk0.5"])
def test_grad_frame_round_trip_topk(wire):
    rng = np.random.default_rng(1)
    g = rng.standard_normal((8, 64)).astype(np.float32)
    arrays, meta, ef = protocol.encode_grad_payload(g, wire, None)
    assert meta["kind"] == "topk" and ef is not None
    buf = protocol.pack_frame(protocol.GRAD, 0, 0, meta=meta, arrays=arrays)
    frame = protocol.unpack_frame(buf[4:])
    out = protocol.decode_grad_payload(frame)
    assert out.shape == g.shape
    # exactly round(frac*d) nonzero entries per row survive
    from repro.parallel.wire import parse_wire_dtype
    _, frac = parse_wire_dtype(wire)
    kk = round(frac * g.shape[-1])
    assert int(np.count_nonzero(out)) <= kk * g.shape[0]
    # what was shipped + what EF retains == the input (telescoping)
    np.testing.assert_allclose(out.astype(np.float32) + ef, g, atol=1e-5)


def test_grad_error_feedback_telescopes_across_rounds():
    """dec1 + dec2 == g1 + g2 - ef2 exactly: no gradient mass is lost,
    only delayed — the streaming twin of ``coded_ppermute_ef``."""
    rng = np.random.default_rng(2)
    g1 = rng.standard_normal((4, 32)).astype(np.float32)
    g2 = rng.standard_normal((4, 32)).astype(np.float32)
    a1, m1, ef1 = protocol.encode_grad_payload(g1, "int8+topk0.25", None)
    d1 = protocol.decode_grad_payload(protocol.unpack_frame(
        protocol.pack_frame(protocol.GRAD, 0, 0, m1, a1)[4:]))
    a2, m2, ef2 = protocol.encode_grad_payload(g2, "int8+topk0.25", ef1)
    d2 = protocol.decode_grad_payload(protocol.unpack_frame(
        protocol.pack_frame(protocol.GRAD, 0, 1, m2, a2)[4:]))
    np.testing.assert_allclose(
        d1.astype(np.float32) + d2.astype(np.float32),
        g1 + g2 - ef2, atol=1e-5)


def test_net_loss_raw_fallback_on_wire():
    """Degenerate block (prime d > 256 -> block 1, 1+4/1 >= itemsize):
    the frame ships RAW and EF passes through unchanged, mirroring the
    in-process ``codec_net_loss`` rule."""
    from repro.parallel.wire import codec_net_loss
    d = 263
    assert codec_net_loss(d, 4)
    rng = np.random.default_rng(3)
    x = rng.standard_normal((2, d)).astype(np.float32)
    arrays, meta = protocol.encode_act_payload(x, "int8")
    assert meta["kind"] == "raw" and set(arrays) == {"raw"}
    frame = protocol.unpack_frame(protocol.pack_frame(
        protocol.ACT, 0, 0, meta, arrays)[4:])
    np.testing.assert_array_equal(protocol.decode_act_payload(frame), x)
    ef_in = np.ones_like(x)
    garrays, gmeta, ef_out = protocol.encode_grad_payload(
        x, "int8+topk0.25", ef_in)
    assert gmeta["kind"] == "raw"
    assert ef_out is ef_in
    gframe = protocol.unpack_frame(protocol.pack_frame(
        protocol.GRAD, 0, 0, gmeta, garrays)[4:])
    np.testing.assert_array_equal(protocol.decode_grad_payload(gframe), x)


def test_frame_header_validation():
    buf = protocol.pack_frame(protocol.HELLO, 1, 0, meta={"a": 1})
    body = bytearray(buf[4:])
    with pytest.raises(ValueError, match="magic"):
        protocol.unpack_frame(b"XXXX" + bytes(body[4:]))
    bad_ver = bytearray(body)
    bad_ver[4] = 99
    with pytest.raises(ValueError, match="version"):
        protocol.unpack_frame(bytes(bad_ver))
    with pytest.raises(ValueError, match="length mismatch"):
        protocol.unpack_frame(bytes(body) + b"\x00")
    # meta survives exactly (JSON-typed)
    frame = protocol.unpack_frame(bytes(body))
    assert frame.meta == {"a": 1}


def test_billed_hop_bytes_matches_hand_math():
    # d=64: block 64; int8 fwd: 1 + 4/64; int8+topk0.25 bwd:
    # 0.25*(1+2) + 4/64  (16 of 64 kept, int16 idx, one f32 row scale)
    n, d = 4 * 16 * 64, 64
    assert protocol.billed_hop_bytes(n, d, "none", 4.0) == 4.0 * n
    assert protocol.billed_hop_bytes(n, d, "int8", 4.0) == \
        pytest.approx((1 + 4 / 64) * n)
    assert protocol.billed_hop_bytes(n, d, "int8+topk0.25", 4.0,
                                     backward=True) == \
        pytest.approx((0.25 * 3 + 4 / 64) * n)


# ---------------------------------------------------------------------------
# Host codec parity vs the jnp kernels
# ---------------------------------------------------------------------------


def test_host_codec_matches_device_int8_exact():
    import jax.numpy as jnp
    from repro.parallel import wire
    rng = np.random.default_rng(4)
    x = rng.standard_normal((6, 16, 64)).astype(np.float32)
    hq, hs = wire.host_encode(x, "int8")
    dq, ds = wire.encode(jnp.asarray(x), "int8")
    np.testing.assert_array_equal(hq, np.asarray(dq))
    np.testing.assert_array_equal(hs, np.asarray(ds))
    hdec = wire.host_decode(hq, hs, np.float32)
    ddec = np.asarray(wire.decode(dq, ds, jnp.float32))
    np.testing.assert_array_equal(hdec, ddec)


def test_host_codec_matches_device_topk_exact():
    import jax.numpy as jnp
    from repro.parallel import wire
    rng = np.random.default_rng(5)
    g = rng.standard_normal((8, 64)).astype(np.float32)
    hq, hidx, hs = wire.host_topk_encode(g, "int8+topk0.25")
    dq, didx, ds = wire.topk_encode(jnp.asarray(g), "int8+topk0.25")
    np.testing.assert_array_equal(hidx, np.asarray(didx))
    np.testing.assert_array_equal(hq, np.asarray(dq))
    np.testing.assert_array_equal(hs, np.asarray(ds))
    hdec = wire.host_topk_decode(hq, hidx, hs, 64, np.float32)
    ddec = np.asarray(wire.topk_decode(dq, didx, ds, 64, jnp.float32))
    np.testing.assert_array_equal(hdec, ddec)


def test_host_codec_fp8_bounded():
    """XLA:CPU's f32->f8 convert rounds near-ties differently from
    ml_dtypes' round-to-nearest-even, so fp8 payloads may differ by one
    ULP; scales are exact and the reconstruction gap stays within one
    quantization step."""
    import jax.numpy as jnp
    from repro.parallel import wire
    rng = np.random.default_rng(6)
    x = rng.standard_normal((4, 16, 64)).astype(np.float32)
    hq, hs = wire.host_encode(x, "fp8")
    dq, ds = wire.encode(jnp.asarray(x), "fp8")
    np.testing.assert_array_equal(hs, np.asarray(ds))
    hdec = wire.host_decode(hq, hs, np.float32)
    ddec = np.asarray(wire.decode(dq, ds, jnp.float32))
    # one e4m3 ULP at the clip bin: 448 = 2^8 * 1.75, 3-bit mantissa
    # -> ULP = 2^8 / 8 = 32 quantizer units
    step = np.abs(hs).max() * 32
    assert float(np.max(np.abs(hdec - ddec))) <= float(step)
    assert float(np.max(np.abs(hdec - x))) < 0.2


# ---------------------------------------------------------------------------
# Dispatcher components over a real loopback socket
# ---------------------------------------------------------------------------


def _fake_split():
    """Minimal SplitSpec stand-in for transport-only dispatcher tests."""
    import jax.numpy as jnp
    import types

    def bs_loss(params, acts, labels):
        return jnp.sum(acts * params["w"]), {}

    return types.SimpleNamespace(bs_loss=bs_loss)


def test_bounded_inbox_backpressure():
    """A client pushing frames faster than the trainer drains them fills
    its bounded inbox: the QoS monitor counts the backpressure event and
    the reader stops enqueueing (inbox never exceeds queue_depth)."""
    import jax.numpy as jnp
    from repro.runtime.bs import BSDispatcher
    from repro.training.optim import adamw

    async def scenario():
        disp = BSDispatcher(_fake_split(), {"w": jnp.ones(())}, adamw(1e-3),
                            n_clients=1, queue_depth=1)
        await disp.start()
        reader, writer = await asyncio.open_connection(disp.host, disp.port)
        writer.write(protocol.pack_frame(protocol.HELLO, 0, 0))
        acts = np.zeros((1, 4, 8), np.float32)
        for step in range(3):
            arrays, meta = protocol.encode_act_payload(acts, "none")
            arrays["labels"] = np.zeros((1, 4), np.int32)
            writer.write(protocol.pack_frame(protocol.ACT, 0, step,
                                             meta, arrays))
        await writer.drain()
        await asyncio.sleep(0.3)        # let the reader hit the full inbox
        inbox, _w = disp._clients[0]
        assert inbox.qsize() == 1       # bounded: depth never exceeded
        assert disp.qos.clients[0].backpressure_events >= 1
        assert disp.qos.clients[0].queue_high_water == 1
        # draining one slot unblocks the reader and admits the next frame
        await inbox.get()
        await asyncio.sleep(0.2)
        assert inbox.qsize() == 1
        writer.close()
        await disp.close()

    run_bounded(scenario())


async def _stream(cfg, *, shapers, steps, wire_dtype="none", lr=1e-3,
                  seed=0, bpc=2, seq=16, cut=2, queue_depth=2,
                  replanner=None, bs_shaper=None, on_started=None):
    """run_streaming with a PER-CLIENT shaper list (ragged arrivals)."""
    import jax
    from repro.models import LM
    from repro.runtime.bs import BSDispatcher
    from repro.runtime.driver import client_batches
    from repro.runtime.ue import UEClient, UESync
    from repro.sl import lm_split
    from repro.training.optim import adamw

    n = len(shapers)
    model = LM(cfg)
    params = model.init(jax.random.key(seed))
    spec = lm_split(model, cut)
    ue_params, bs_params = spec.split_params(params)
    disp = BSDispatcher(spec, bs_params, adamw(lr), n_clients=n,
                        wire_dtype=wire_dtype, queue_depth=queue_depth,
                        replanner=replanner, shaper=bs_shaper)
    sync = UESync(ue_params, adamw(lr), n)
    ue_fwd = jax.jit(spec.ue_fwd)

    def pullback(p, tokens, g):
        return jax.vjp(lambda q: spec.ue_fwd(q, tokens), p)[1](g)[0]

    ue_pb = jax.jit(pullback)
    clients = [UEClient(cid, spec,
                        client_batches(cfg, cid, n, bpc, seq, seed),
                        sync, wire_dtype=wire_dtype, shaper=shapers[cid],
                        ue_fwd=ue_fwd, ue_pullback=ue_pb)
               for cid in range(n)]
    host, port = await disp.start()
    if on_started is not None:
        on_started(disp, clients)
    try:
        await asyncio.gather(disp.train(steps),
                             *(c.run(host, port, steps) for c in clients))
    finally:
        await disp.close()
    return disp, sync, clients


def test_ragged_arrival_order_independence():
    """Slowing down a DIFFERENT client must not change the trained
    result: per-arrival micro-steps all use the pre-round params and the
    round reduction is in sorted-client order."""
    from repro.wireless import LinkShaper
    cfg = _tiny_cfg()
    slow, fast = LinkShaper(2e5), None
    d1, s1, _ = run_bounded(_stream(cfg, shapers=[slow, fast, fast],
                                    steps=3))
    d2, s2, _ = run_bounded(_stream(cfg, shapers=[fast, fast, slow],
                                    steps=3))
    np.testing.assert_allclose(d1.losses, d2.losses, rtol=0, atol=1e-6)
    import jax
    for a, b in zip(jax.tree.leaves(d1.bs_params),
                    jax.tree.leaves(d2.bs_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-7)
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-7)


@pytest.mark.parametrize("wire", CODECS)
def test_wire_honesty_on_socket(wire):
    """Measured codec-payload bytes of every hop that crossed the REAL
    socket match the planner's ``wire_bytes_per_element(_bwd)`` billing
    at 1% rtol (byte-exact in practice; framing/labels are accounted
    separately as overhead, mirroring ``hop_overhead_s``)."""
    from repro.runtime.driver import run_streaming
    cfg = _tiny_cfg(d_model=64, vocab=64)
    res = run_bounded(run_streaming(
        cfg, cut=2, n_clients=2, steps=2, batch_per_client=2, seq=16,
        wire_dtype=wire))
    assert all(np.isfinite(res["losses"]))
    honesty = res["wire_honesty"]
    assert honesty["uplink"] and honesty["downlink"]
    for direction, rows in honesty.items():
        for row in rows:
            assert row["ok"], (wire, direction, row)
    qos = res["qos"]
    json.dumps(qos)                      # snapshot is plain JSON
    assert qos["rounds"] == 2
    assert qos["totals"]["frames_in"] == 2 * 2
    assert sum(c["straggler_rounds"]
               for c in qos["clients"].values()) == qos["rounds"]


def test_client_batches_union_is_full_batch():
    from repro.data import lm_batch_for
    from repro.runtime.driver import client_batches
    cfg = _tiny_cfg()
    n, bpc, seq, seed = 3, 2, 16, 7
    iters = [client_batches(cfg, cid, n, bpc, seq, seed)
             for cid in range(n)]
    for step in range(2):
        shards = [next(it) for it in iters]
        ref = lm_batch_for(cfg, n * bpc, seq, seed=seed + step)
        np.testing.assert_array_equal(
            np.concatenate([t for t, _l in shards]), ref["tokens"])
        np.testing.assert_array_equal(
            np.concatenate([l for _t, l in shards]), ref["labels"])


# ---------------------------------------------------------------------------
# Slow lane: e2e parity + measured-hop re-planning
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_e2e_four_clients_matches_joint_training():
    """4 UE clients x 20 steps over loopback: finite losses, and the
    whole trajectory (losses AND final params) matches joint full-batch
    training of the unsplit objective to f32 reduction-order tolerance.
    The in-process pipeline path equals this same joint step
    (tests/test_pipeline.py), so this transitively pins streaming ==
    pipeline."""
    import jax
    import jax.numpy as jnp
    from repro.data import lm_batch_for
    from repro.models import LM
    from repro.runtime.driver import run_streaming
    from repro.sl import lm_split
    from repro.training.optim import adamw

    cfg = _tiny_cfg()
    STEPS, N, BPC, SEQ, SEED, LR, CUT = 20, 4, 2, 16, 0, 1e-3, 2
    res = run_bounded(run_streaming(
        cfg, cut=CUT, n_clients=N, steps=STEPS, batch_per_client=BPC,
        seq=SEQ, seed=SEED, wire_dtype="none", lr=LR))
    assert len(res["losses"]) == STEPS
    assert all(np.isfinite(res["losses"]))
    # every client saw every round's loss
    for cid, cl in res["client_losses"].items():
        assert len(cl) == STEPS

    model = LM(cfg)
    params = model.init(jax.random.key(SEED))
    spec = lm_split(model, CUT)
    ue, bs = spec.split_params(params)
    opt = adamw(LR)
    opt_ue, opt_bs = opt.init(ue), opt.init(bs)

    def loss_fn(ue, bs, tokens, labels):
        return spec.bs_loss(bs, spec.ue_fwd(ue, tokens), labels)[0]

    grad = jax.jit(jax.value_and_grad(loss_fn, argnums=(0, 1)))
    upd = jax.jit(opt.update)
    ref_losses = []
    for step in range(STEPS):
        b = lm_batch_for(cfg, N * BPC, SEQ, seed=SEED + step)
        loss, (gue, gbs) = grad(ue, bs, b["tokens"], b["labels"])
        s = jnp.asarray(step, jnp.int32)
        ue, opt_ue = upd(gue, opt_ue, ue, s)
        bs, opt_bs = upd(gbs, opt_bs, bs, s)
        ref_losses.append(float(loss))

    np.testing.assert_allclose(res["losses"], ref_losses, atol=1e-5)
    for a, b_ in zip(jax.tree.leaves(res["params"]["ue"]),
                     jax.tree.leaves(ue)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-5)
    for a, b_ in zip(jax.tree.leaves(res["params"]["bs"]),
                     jax.tree.leaves(bs)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-5)


@pytest.mark.slow
def test_replanner_tracks_injected_delay_change():
    """AC: the re-planner's ``PlanInputs`` reflect a mid-run artificial
    delay change purely from MEASURED socket hops.  A ``LinkShaper`` at
    bw0 is dropped to bw0/4 after round 5; the ``LinkEstimator`` (fed
    only via ``observe_hop`` from frame timestamps — ``observe_bandwidth``
    is spied to prove no scripted feed) must show the bandwidth drop and
    ``refreshed_inputs().link_s`` must grow accordingly."""
    from repro.analysis.autotune import WIRE_AUTO, PlanInputs, choose_plan
    from repro.runtime.driver import run_streaming
    from repro.training.replan import (LinkEstimator, ReplanConfig,
                                       Replanner)
    from repro.wireless import LinkShaper

    cfg = _tiny_cfg()
    bw0 = 1e5
    shaper = LinkShaper(bw0)
    inp = PlanInputs(num_stages=2, stage_fwd_s=0.1, stage_bwd_s=0.2,
                     link_s=0.01, hop_overhead_s=0.002, k_cap=16,
                     v_cap=4, num_layers=8, act_bytes=2.0,
                     act_hop_bytes=4.0e8, d_model=1024)
    rp = Replanner(inp, choose_plan(inp, wire_candidates=WIRE_AUTO).plan,
                   ReplanConfig(every=5, hysteresis=0.1))
    # small window so the post-change samples dominate the fit quickly
    rp.link = LinkEstimator(ewma=0.7, window=8)
    scripted_calls = []
    orig_bw = rp.link.observe_bandwidth
    rp.link.observe_bandwidth = (
        lambda *a, **k: scripted_calls.append(a) or orig_bw(*a, **k))

    snaps = {}

    def on_started(disp, clients):
        async def watch():
            while len(disp.losses) < 5:
                await asyncio.sleep(0.01)
            snaps["link_s"] = rp.refreshed_inputs().link_s
            snaps["bw"] = rp.link.hints()["link_bw_Bps"]
            shaper.set_rate(bw0 / 4)
        asyncio.ensure_future(watch())

    run_bounded(run_streaming(
        cfg, cut=2, n_clients=2, steps=10, batch_per_client=2, seq=16,
        seed=0, wire_dtype="none", lr=1e-3, shaper=shaper, replanner=rp,
        on_started=on_started))

    assert not scripted_calls            # nothing scripted fed the link
    assert len(rp.link._samples) > 0     # hops were measured
    bw_after = rp.link.hints()["link_bw_Bps"]
    link_s_after = rp.refreshed_inputs().link_s
    # a 4x rate drop must show through scheduling/compute noise
    assert bw_after < 0.5 * snaps["bw"], (bw_after, snaps["bw"])
    assert link_s_after > 2.0 * snaps["link_s"], \
        (link_s_after, snaps["link_s"])
    # and the fold-in really derives link_s from the measured bandwidth
    assert link_s_after == pytest.approx(inp.act_hop_bytes / bw_after)
