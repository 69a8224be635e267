"""Time the parts of the boolean sparse sweep form on one chip, on CSR
lanes and on destination rows of several widths.

    PYTHONPATH=src python3 -m tools.sparse_rows_probe --config kron_s15 \
        --sources 128 --widths 4,8,16
    PYTHONPATH=src python3 -m tools.sparse_rows_probe --grid 1024 \
        --sources 64 --widths 8

from the root of a checkout, on a machine with a TPU.  It builds the
configuration's graph (``bench/configs/<config>.json``) from ``--seed``
on the device, or a ``--grid`` side x side 4-connected grid (a road
network's degrees), and times, each under ``jit`` and by the median of
``--reps`` calls on the host clock after a warm-up call:

  gather_lanes        ``f[:, src]``, one frontier byte per CSR lane
  gather_rows.W       ``f[:, row_src]`` at the ``(W, R)`` row slots
  gather_or_rows.W    the same, OR-ed over each row -> ``(S, R)``
  scatter_lanes       scatter-max of ``(S, m_pad)`` at the unsorted ``dst``
  scatter_rows.W      scatter-max of ``(S, R)`` at the sorted ``row_dst``
  sweep_lanes         one whole sparse sweep on lanes (the form before
                      destination rows, kept here as the baseline)
  sweep_rows.W        one whole sparse sweep, rows of width W
  layout_rows.W       building the layout from the CSC arrays

and checks that each row sweep equals the lane sweep, entry for entry,
parents included where the lane form's ``(S, m_pad)`` int32 parent
candidates take under 2 GiB.  Prints one JSON line; exits 2 without a
TPU and 1 when a row sweep differs.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def timed(fn, *args, reps: int) -> float:
    """Median milliseconds of ``fn(*args)`` over ``reps`` calls, after
    one call that compiles and warms it."""
    import jax
    jax.block_until_ready(fn(*args))
    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        samples.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(samples)


def lane_form(g, parent: bool):
    """The sparse sweep on CSR lanes: one scatter update per lane at the
    unsorted destinations."""
    import jax.numpy as jnp

    def sweep(f, d, p, step):
        active = f[..., g.src] != 0
        hits = jnp.zeros(d.shape, jnp.bool_).at[..., g.dst].max(active)
        new = hits & (d < 0)
        if parent:
            pcand = jnp.full(d.shape, -1, jnp.int32).at[..., g.dst].max(
                jnp.where(active, g.src, -1))
            p = jnp.where(new, pcand, p)
        return new.astype(jnp.int8), jnp.where(new, step, d), p
    return sweep


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    graph = ap.add_mutually_exclusive_group(required=True)
    graph.add_argument("--config")
    graph.add_argument("--grid", type=int)
    ap.add_argument("--sources", type=int, required=True)
    ap.add_argument("--widths", default="4,8,16")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    from repro.core import sweep as S

    if args.grid:
        from repro.graph import generators as gen
        g = gen.grid2d(args.grid, args.grid)
        name = f"grid{args.grid}"
    else:
        from bench.generators import kronecker
        config = json.loads((ROOT / "bench" / "configs"
                             / f"{args.config}.json").read_text())
        g = kronecker.build(args.seed, config["graph"])
        name = args.config
    n, m_pad, s = g.n_nodes, g.m_pad, args.sources
    n_pad = g.n_padded()
    rng = np.random.default_rng(args.seed)
    # a mid-search state: a tenth of the vertices in the frontier, half
    # reached; the pad columns neither
    f = (rng.random((s, n_pad)) < 0.1).astype(np.int8)
    d = np.where(rng.random((s, n_pad)) < 0.5, -1, 1).astype(np.int32)
    f[:, n:], d[:, n:] = 0, 0
    f, d = jnp.asarray(f), jnp.asarray(d)
    p = jnp.full((s, n_pad), -1, jnp.int32)
    step = jnp.int32(3)
    out = {"graph": name, "seed": args.seed, "sources": s,
           "n": n, "lanes": int(g.n_edges), "m_pad": m_pad,
           "device": jax.devices()[0].device_kind, "ms": {}, "rows": {},
           "equal": {}}
    ms = out["ms"]

    def row_form(rows, parent=False):
        return jax.jit(S.boolean_forms(
            None, None, rows, n_pad=n_pad, s=s,
            track_parent=parent)[S.SPARSE])

    ms["gather_lanes"] = timed(jax.jit(lambda f, i: f[:, i]), f, g.src,
                               reps=args.reps)
    hit_lanes = jnp.asarray(rng.random((s, m_pad)) < 0.3)
    ms["scatter_lanes"] = timed(jax.jit(
        lambda h, i: jnp.zeros((s, n_pad), bool).at[:, i].max(h)),
        hit_lanes, g.dst, reps=args.reps)
    del hit_lanes
    ms["sweep_lanes"] = timed(jax.jit(lane_form(g, False)), f, d, p, step,
                              reps=args.reps)
    parent = s * m_pad * 4 < 2 ** 31
    out["parents_checked"] = parent
    want = [np.asarray(x)
            for x in jax.jit(lane_form(g, parent))(f, d, p, step)]

    for w in (int(x) for x in args.widths.split(",")):
        build = jax.jit(lambda a, b: S._dst_rows(a, b, n_real=n, width=w))
        ms[f"layout_rows.{w}"] = timed(build, g.indptr_t, g.indices_t,
                                       reps=args.reps)
        rows = build(g.indptr_t, g.indices_t)
        row_src, row_dst = rows
        out["rows"][w] = {
            "rows": int(row_src.shape[1]),
            "real_rows": int(jnp.sum(row_dst < n)),
            "lane_fill": float(g.n_edges / row_src.size)}
        ms[f"gather_rows.{w}"] = timed(jax.jit(lambda f, i: f[:, i]), f,
                                       row_src, reps=args.reps)
        ms[f"gather_or_rows.{w}"] = timed(jax.jit(
            lambda f, i: jnp.any(f[:, i] != 0, axis=-2)), f, row_src,
            reps=args.reps)
        hit_rows = jnp.asarray(rng.random((s, row_src.shape[1])) < 0.3)
        ms[f"scatter_rows.{w}"] = timed(jax.jit(
            lambda h, i: jnp.zeros((s, n_pad), bool).at[:, i].max(
                h, indices_are_sorted=True)), hit_rows, row_dst,
            reps=args.reps)
        del hit_rows
        ms[f"sweep_rows.{w}"] = timed(row_form(rows), f, d, p, step,
                                      reps=args.reps)
        got = [np.asarray(x) for x in row_form(rows, parent)(f, d, p, step)]
        out["equal"][w] = all(np.array_equal(a, b)
                              for a, b in zip(want, got))
        del rows, row_src, row_dst, got
    print(json.dumps(out))
    return 0 if all(out["equal"].values()) else 1


if __name__ == "__main__":
    sys.exit(main())
