"""Regenerate/validate the checked-in ``hlo_current.txt`` parser fixture
from its probe program, so fixture drift is a script run instead of a
manual capture.

``hlo_current.txt`` holds the installed JAX's spelling: async
``-start/-done`` pairs, iota replica_groups, bf16, a scan lowered to a
``while`` with ``known_trip_count``.  (``hlo_legacy_0437.txt`` is text
captured from jax 0.4.37; it stays as a parser input and has no probe.)

::

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python tests/fixtures/regen_hlo_fixtures.py --check
    ...                                                           --write

``--check`` regenerates the text in memory and asserts the structural
invariants the parser tests rely on hold on the FRESH text.  ``--write``
overwrites the fixture file with the fresh text.
"""
from __future__ import annotations

import argparse
import os
import sys

FIXDIR = os.path.dirname(os.path.abspath(__file__))


def _force_host_devices(n=4):
    if "jax" in sys.modules:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}").strip()


def generate():
    """Compile this leg's probe program; returns (fixture_name, text)."""
    _force_host_devices(4)
    import jax
    import jax.numpy as jnp

    from repro.parallel import compat
    from repro.parallel.compat import PartitionSpec as P

    mesh = compat.make_mesh((2, 2), ("a", "b"))
    # mirror of the committed hlo_current.txt probe: a 9-trip scan whose
    # body dots and ppermutes (lowers to a while with known_trip_count),
    # plus an entry-level all_gather and a closing psum

    def shmap_body(x, w):
        g = jax.lax.all_gather(w, "b", axis=0, tiled=True)

        def step(c, _):
            y = jnp.dot(c, w)
            return jax.lax.ppermute(y, "a", ((0, 1), (1, 0))), None
        out, _ = jax.lax.scan(step, x, None, length=9)
        return jax.lax.psum(out, "b") + 0.0 * g[: x.shape[0]]

    def train_step(x, w):
        return compat.shard_map(
            shmap_body, mesh,
            in_specs=(P("a", None), P(None, "b")),
            out_specs=P("a", "b"))(x, w)
    # w's 'b' shard is [64, 64], so the scan carry keeps its [8, 64] shape
    x = jnp.zeros((16, 64), jnp.bfloat16)
    w = jnp.zeros((64, 128), jnp.bfloat16)
    text = jax.jit(train_step).lower(x, w).compile().as_text()
    return "hlo_current.txt", text


def check(text: str, name: str):
    """Structural invariants the parser tests rely on, asserted on the
    FRESH text."""
    from repro.analysis.hlo_costs import (analyze, parse_hlo,
                                          source_target_pairs)
    comps = parse_hlo(text)
    assert comps, f"{name}: no computations parsed from fresh text"
    pairs = []
    for instrs in comps.values():
        for ins in instrs:
            if ins.opcode.startswith("collective-permute") \
                    and not ins.opcode.endswith("-done"):
                pairs = source_target_pairs(ins.rest)
    assert sorted(pairs) == [(0, 2), (1, 3), (2, 0), (3, 1)], (
        f"{name}: ppermute pairs {pairs} != the a-axis exchange on the "
        "2x2 probe mesh")
    res = analyze(text)
    assert res["coll_by_kind"]["collective-permute"] > 0
    assert res["coll_by_kind"]["all-gather"] > 0
    assert res["coll_by_kind"]["all-reduce"] > 0
    assert res["n_while"] >= 1, (
        f"{name}: scan did not lower to a while — the trip-count "
        "invariant the parser tests pin is gone")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--write", action="store_true",
                    help="overwrite this leg's fixture with fresh text")
    ap.add_argument("--check", action="store_true",
                    help="regenerate in memory and validate (default)")
    args = ap.parse_args(argv)
    name, text = generate()
    check(text, name)
    if args.write:
        with open(os.path.join(FIXDIR, name), "w") as f:
            f.write(text)
        print(f"wrote {name} ({len(text)} bytes)")
    else:
        print(f"{name}: fresh text validates ({len(text)} bytes)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
