"""Shared by the harness tests: tiny cells, a run without the look for
chips, and faults planted under the timed path.

Every fault wraps the engine's per-batch program
(``repro.core.engine._run_batch``), which both the facade's ``apsp`` and
the serving tier's sweep flushes call, and breaks the state it returns.
"""
import json
import pathlib
import types

import jax
import jax.numpy as jnp

from bench import run as bench_run

ROOT = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
GRAPH = {"scale": 8, "edge_factor": 16, "a": 0.57, "b": 0.19, "c": 0.19,
         "m_pad": 8192}


def tiny(cell_name: str):
    """(cell, configuration, traffic) of a cell, at a test's size."""
    cell, config, traffic = bench_run.find_cell(SPEC, cell_name)
    config = dict(config, graph=GRAPH,
                  facade=dict(config["facade"], source_batch=16),
                  serve=dict(config.get("serve", {}), max_batch=8,
                             n_landmarks=4))
    if traffic["driver"] == "closed_batches":
        traffic = dict(traffic, batch=16)
    else:
        traffic = dict(traffic, rate=150, warm_seconds=0.3)
    return cell, config, traffic


def run(cell_name: str, seed: int = 7, seconds: float = 0.5,
        options=None) -> dict:
    """A whole run but the look for chips -> its result line."""
    cell, config, traffic = tiny(cell_name)
    if options:
        config = dict(config, facade={**config["facade"], **options})
    args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=0,
                                 keep_trace=None)
    return bench_run.run_cell(SPEC, cell, config, traffic, args,
                              jax.devices())


def _initial(st, sources):
    """The distances as they stand before the first sweep."""
    n_pad = st.dist.shape[1]
    hit = jnp.arange(n_pad)[None, :] == sources[:, None]
    return jnp.where(hit, 0, -1).astype(st.dist.dtype)


def unchanged(st, sources, n_valid):
    return st._replace(dist=_initial(st, sources))


def half_left_out(st, sources, n_valid):
    # the second half of the batch's real rows is never searched
    rows = jnp.arange(st.dist.shape[0])[:, None]
    return st._replace(dist=jnp.where(rows >= n_valid // 2,
                                      _initial(st, sources), st.dist))


def answer_altered(st, sources, n_valid):
    return st._replace(dist=st.dist + (st.dist > 0))


FAULTS = {"unchanged_state": unchanged, "half_batch_left_out": half_left_out,
          "answer_altered": answer_altered}


def plant(monkeypatch, fault) -> None:
    import repro.core.engine as engine
    orig = engine._run_batch

    def broken(adj, adj_pull, src_idx, dst_idx, deg, sources, n_valid,
               **kw):
        st = orig(adj, adj_pull, src_idx, dst_idx, deg, sources, n_valid,
                  **kw)
        return fault(st, sources, n_valid)

    monkeypatch.setattr(engine, "_run_batch", broken)
