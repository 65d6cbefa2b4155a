"""BS-side dispatcher: socket server, bounded inboxes, micro-batch
aggregation, and the measured-hop feed into the online re-planner.

Data path per training round (the C2P2SL server pipeline):

* every UE's ACT frame lands in that client's BOUNDED inbox
  (``asyncio.Queue(maxsize=queue_depth)``).  A full inbox blocks the
  per-connection reader coroutine, which stops draining the socket —
  TCP backpressure then throttles the UE's ``drain()``.  Clients may
  run ahead of the trainer by at most ``queue_depth`` rounds.
* the aggregator takes exactly ONE frame per client per round, in
  ARRIVAL order: each arrival immediately runs the BS-side micro step
  (forward + backward of blocks[l:] on that client's shard) and ships
  the coded cut-activation gradient straight back — server compute
  overlaps the stragglers' uplinks, which is the pipeline-parallel
  schedule of the paper, event-driven instead of simulated.
* the optimizer update applies once per round on the sorted-client mean
  of the per-shard grads, so the result is independent of arrival
  order (tested).

Every hop is measured: uplink frames carry ``t_send`` (one host, one
monotonic clock), downlink times are measured by the UE and reported in
its next frame; both feed ``Replanner.observe_hop`` /
``LinkEstimator.observe_hop`` — the re-planner's ``PlanInputs`` then
track the REAL transport (or the ``LinkShaper``-emulated channel), with
no scripted ``BandwidthTrace`` anywhere in the loop.
"""
from __future__ import annotations

import asyncio
import time

import numpy as np

from repro.runtime import protocol
from repro.runtime.qos import QoSMonitor


class BSDispatcher:
    def __init__(self, split, bs_params, opt, *, n_clients: int,
                 wire_dtype: str = "none", queue_depth: int = 2,
                 replanner=None, shaper=None, qos: QoSMonitor | None = None,
                 stall_after_s: float = 0.25,
                 host: str = "127.0.0.1", port: int = 0):
        import jax
        import jax.numpy as jnp
        self.split = split
        self.bs_params = bs_params
        self.opt = opt
        self.opt_state = opt.init(bs_params)
        self.n_clients = int(n_clients)
        self.wire_dtype = str(wire_dtype)
        self.queue_depth = int(queue_depth)
        self.replanner = replanner
        self.shaper = shaper
        self.qos = qos or QoSMonitor(stall_after_s=stall_after_s)
        self.stall_after_s = float(stall_after_s)
        self.host, self.port = host, int(port)
        self._server = None
        self._clients: dict = {}          # cid -> (inbox, writer)
        self._all_joined = asyncio.Event()
        self._ef: dict = {}               # cid -> per-client EF residual
        self.losses: list = []
        # wire-honesty audit: (payload_bytes, n_elements, d, act_itemsize)
        self.hop_audit = {"uplink": set(), "downlink": set()}
        self._jnp = jnp

        def micro(bs_params, acts, labels):
            (loss, _mets), (bs_g, act_g) = jax.value_and_grad(
                split.bs_loss, argnums=(0, 1), has_aux=True)(
                    bs_params, acts, labels)
            return loss, bs_g, act_g

        self._micro = jax.jit(micro)

        def mean_update(grads_list, opt_state, params, step):
            mean = jax.tree.map(
                lambda *gs: sum(gs[1:], gs[0]) / len(gs), *grads_list)
            return opt.update(mean, opt_state, params, step)

        self._mean_update = jax.jit(mean_update)

    # -- transport -----------------------------------------------------------

    async def start(self):
        self._server = await asyncio.start_server(
            self._handle_client, self.host, self.port)
        self.port = self._server.sockets[0].getsockname()[1]
        return self.host, self.port

    async def close(self):
        if self._server is not None:
            self._server.close()
            # a reader blocked on a full inbox never sees its client leave,
            # so drop every connection here before waiting for them
            for _inbox, writer in self._clients.values():
                writer.close()
            await self._server.wait_closed()

    def _observe_hop(self, nbytes, seconds):
        if self.replanner is not None and nbytes and seconds \
                and seconds > 0:
            self.replanner.observe_hop(float(nbytes), float(seconds))

    def _observe_frame(self, frame: protocol.Frame, t_recv: float) -> None:
        t_send = frame.meta.get("t_send")
        if t_send is not None:
            self._observe_hop(frame.wire_nbytes, t_recv - float(t_send))
        # the UE piggybacks its measurement of our PREVIOUS downlink
        self._observe_hop(frame.meta.get("dl_nbytes"),
                          frame.meta.get("dl_s"))

    async def _handle_client(self, reader, writer):
        # Close our end when the client leaves: ``Server.wait_closed()``
        # (Python >= 3.12.1) waits for every connection, so an open writer
        # would hang ``close()``.
        try:
            await self._serve_client(reader, writer)
        finally:
            writer.close()

    async def _serve_client(self, reader, writer):
        hello = await protocol.read_frame(reader)
        if hello.ftype != protocol.HELLO:
            raise ValueError(
                f"client handshake must be HELLO, got ftype={hello.ftype}")
        cid = hello.client
        inbox = asyncio.Queue(maxsize=self.queue_depth)
        self._clients[cid] = (inbox, writer)
        if len(self._clients) >= self.n_clients:
            self._all_joined.set()
        while True:
            try:
                frame = await protocol.read_frame(reader)
            except (asyncio.IncompleteReadError, ConnectionError):
                break
            t_recv = time.monotonic()
            self._observe_frame(frame, t_recv)
            if frame.ftype == protocol.BYE:
                break
            if frame.ftype != protocol.ACT:
                continue                   # STATS etc.: telemetry only
            shape = frame.meta["shape"]
            self.hop_audit["uplink"].add(
                (frame.payload_nbytes,
                 int(np.prod(shape, dtype=np.int64)), int(shape[-1]),
                 int(protocol._np_dtype(frame.meta["dtype"]).itemsize)))
            self.qos.record_arrival(cid, frame.wire_nbytes,
                                    frame.payload_nbytes, frame.aux_nbytes)
            if inbox.full():
                self.qos.record_backpressure(cid)
            await inbox.put(frame)
            self.qos.record_queue_depth(cid, inbox.qsize())

    async def _send_grad(self, cid: int, step: int, act_grad, loss) -> None:
        _inbox, writer = self._clients[cid]
        g = np.asarray(act_grad)
        arrays, meta, new_ef = protocol.encode_grad_payload(
            g, self.wire_dtype, self._ef.get(cid))
        self._ef[cid] = new_ef
        meta["loss"] = float(loss)
        meta["t_send"] = time.monotonic()
        frame = protocol.pack_frame(protocol.GRAD, cid, step,
                                    meta=meta, arrays=arrays)
        payload_nbytes = sum(a.nbytes for n, a in arrays.items()
                             if n in protocol.PAYLOAD_SECTIONS)
        self.hop_audit["downlink"].add(
            (payload_nbytes, int(g.size), int(g.shape[-1]),
             int(g.dtype.itemsize)))
        if self.shaper is not None:
            await asyncio.sleep(self.shaper.delay_s(len(frame)))
        writer.write(frame)
        await writer.drain()
        self.qos.record_send(cid, len(frame), payload_nbytes)

    # -- training ------------------------------------------------------------

    async def train(self, steps: int):
        """Run ``steps`` aggregation rounds; returns per-round losses."""
        await self._all_joined.wait()
        for step in range(steps):
            per_client: dict = {}
            pending = {
                asyncio.ensure_future(inbox.get()): cid
                for cid, (inbox, _w) in self._clients.items()}
            straggler = None
            while pending:
                done, _ = await asyncio.wait(
                    pending, timeout=self.stall_after_s,
                    return_when=asyncio.FIRST_COMPLETED)
                if not done:
                    for cid in pending.values():
                        self.qos.record_stall(cid)
                    continue
                for task in done:
                    cid = pending.pop(task)
                    frame = task.result()
                    inbox, _w = self._clients[cid]
                    self.qos.record_queue_depth(cid, inbox.qsize())
                    acts = protocol.decode_act_payload(frame)
                    labels = frame.arrays["labels"]
                    loss, bs_g, act_g = self._micro(
                        self.bs_params, acts, labels)
                    per_client[cid] = (float(loss), bs_g)
                    straggler = cid
                    # 1F1B, event-driven: the gradient leaves NOW, while
                    # other clients' uplinks are still in flight
                    await self._send_grad(cid, step, act_g, loss)
            ordered = sorted(per_client)
            grads_list = [per_client[c][1] for c in ordered]
            step_arr = self._jnp.asarray(step, self._jnp.int32)
            self.bs_params, self.opt_state = self._mean_update(
                grads_list, self.opt_state, self.bs_params, step_arr)
            self.losses.append(
                float(np.mean([per_client[c][0] for c in ordered])))
            self.qos.record_round(straggler)
        return self.losses

    # -- audits --------------------------------------------------------------

    def wire_honesty(self, rtol: float = 0.01) -> dict:
        """Measured socket payload bytes per hop vs planner billing.

        Returns per-direction rows of (measured, billed, ok); ``ok``
        within ``rtol`` is the off-simulator honesty acceptance gate.
        """
        out = {}
        for direction, rows in self.hop_audit.items():
            ent = []
            for payload_nbytes, n_el, d, itemsize in sorted(rows):
                billed = protocol.billed_hop_bytes(
                    n_el, d, self.wire_dtype, float(itemsize),
                    backward=(direction == "downlink"))
                ent.append({
                    "measured_bytes": int(payload_nbytes),
                    "billed_bytes": billed,
                    "ok": bool(abs(payload_nbytes - billed)
                               <= rtol * max(billed, 1.0)),
                })
            out[direction] = ent
        return out
