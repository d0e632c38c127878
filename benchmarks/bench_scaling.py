"""Graph-size scaling: the sparse-vs-dense crossover curve.

    PYTHONPATH=src python -m benchmarks.bench_scaling
    PYTHONPATH=src python -m benchmarks.bench_scaling --smoke
    PYTHONPATH=src python -m benchmarks.bench_scaling --json scaling.json

The paper's g1..g3 observation — "acceleration from the GPU increases
with graph size" — holds for *dense* states only while the closure's
occupied fraction stays high.  This bench sweeps an (n × density) grid
over the shared sparse-graph families (tests/helpers.py: chain,
community, power_law) and times, per point,

  sparse_s  ``blocksparse_closure_state`` — the compacted bit-tile
            fixpoint whose state and work are proportional to occupied
            blocks, never materializing the dense (N, n, n) tensor;
  dense_s   the ``dense_step`` fixpoint over the padded dense tensor
            (exact iteration count included).  Above ``--dense-max``
            nodes the full dense run is extrapolated from a warm single
            step (``dense_estimated: true``): per-step cost is flat
            across iterations, so step-time x iteration-count is tight.

Each row also reports the occupied-block fraction, so the crossover is
attributable: block-sparse wins exactly where occupied_frac collapses
(large n, low density), and loses to dense where the closure fills in.
Emits ONE JSON object with --json.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

from repro.core import closure
from repro.core.blocksparse import DEFAULT_TILE, blocksparse_closure_state
from repro.core.grammar import Grammar
from repro.core.matrices import ProductionTables, init_matrix

_TESTS = Path(__file__).resolve().parent.parent / "tests"
if str(_TESTS) not in sys.path:
    sys.path.insert(0, str(_TESTS))
from helpers import sparse_graph  # noqa: E402  (shared generators)

# Same-generation-flavored grammar over the generators' t0/t1 labels:
# nesting keeps the fixpoint iterating instead of converging in one step.
GRAMMAR = "S -> t0 S t1 | t0 t1"

CSV_HEADER = (
    "family,n,density,n_edges,iters,occupied_blocks,occupied_frac,"
    "state_mib,dense_mib,sparse_ms,dense_ms,dense_est"
)


def _dense_fixpoint(T0, tables) -> tuple[int, float]:
    """(iterations, seconds) of the warm dense fixpoint loop."""
    import jax.numpy as jnp

    closure.dense_step(T0, tables).block_until_ready()  # compile
    t0 = time.perf_counter()
    T, it = T0, 0
    while True:
        T2 = closure.dense_step(T, tables)
        it += 1
        if bool(jnp.array_equal(T2, T)):
            return it, time.perf_counter() - t0
        T = T2


def _dense_step_time(T0, tables) -> float:
    """Warm per-iteration dense step cost (for the extrapolated rows)."""
    closure.dense_step(T0, tables).block_until_ready()  # compile
    t0 = time.perf_counter()
    closure.dense_step(T0, tables).block_until_ready()
    return time.perf_counter() - t0


def bench_point(
    family: str,
    n: int,
    density: float,
    g,
    tables: ProductionTables,
    tile: int,
    dense_max: int,
    iters_hint: int,
) -> dict:
    graph = sparse_graph(family, np.random.default_rng(n), n, density)

    # sparse side: warmup run compiles the chunked contraction, second
    # run is the timed one (both full closures — state is rebuilt).
    blocksparse_closure_state(graph, g, tile=tile)
    t0 = time.perf_counter()
    state = blocksparse_closure_state(graph, g, tile=tile)
    sparse_s = time.perf_counter() - t0

    grid = state.grid
    dense_bytes = g.n_nonterms * n * n  # bool tensor the dense path holds
    out = {
        "family": family,
        "n": n,
        "density": density,
        "n_edges": graph.n_edges,
        "occupied_blocks": state.occupied,
        "occupied_frac": round(
            state.occupied / (g.n_nonterms * grid * grid), 4
        ),
        "state_bytes": state.nbytes(),
        "dense_bytes": dense_bytes,
        "sparse_s": round(sparse_s, 4),
    }

    T0 = init_matrix(graph, g)
    if n <= dense_max:
        iters, dense_s = _dense_fixpoint(T0, tables)
        out["dense_estimated"] = False
    else:
        iters = iters_hint
        dense_s = _dense_step_time(T0, tables) * iters
        out["dense_estimated"] = True
    out["iters"] = iters
    out["dense_s"] = round(dense_s, 4)
    out["speedup"] = round(dense_s / max(sparse_s, 1e-9), 2)
    return out


def run_grid(
    families: list[str],
    sizes: list[int],
    densities: list[float],
    tile: int,
    dense_max: int,
) -> list[dict]:
    g = Grammar.from_text(GRAMMAR).to_cnf()
    tables = ProductionTables.from_grammar(g)
    results: list[dict] = []
    iters_hint = 0
    for n in sorted(sizes):
        for family in families:
            # chain density is 1 edge/node by construction — one point
            dens = [1.0] if family == "chain" else densities
            for density in dens:
                r = bench_point(
                    family, n, density, g, tables, tile, dense_max,
                    # extrapolated rows reuse the deepest measured
                    # fixpoint (iteration count grows ~log n, so the
                    # hint under-counts — the estimate stays honest)
                    iters_hint=max(iters_hint, 1),
                )
                if not r["dense_estimated"]:
                    iters_hint = max(iters_hint, r["iters"])
                results.append(r)
    return results


def _csv(results: list[dict], rows: list[str]) -> list[str]:
    rows.append(CSV_HEADER)
    for r in results:
        rows.append(
            f"{r['family']},{r['n']},{r['density']},{r['n_edges']},"
            f"{r['iters']},{r['occupied_blocks']},{r['occupied_frac']},"
            f"{r['state_bytes'] / 2**20:.2f},{r['dense_bytes'] / 2**20:.2f},"
            f"{r['sparse_s'] * 1e3:.1f},{r['dense_s'] * 1e3:.1f},"
            f"{int(r['dense_estimated'])}"
        )
    return rows


def main(rows: list[str] | None = None) -> list[str]:
    """run.py's [scaling] section: a quick grid, CSV lines returned."""
    rows = rows if rows is not None else []
    results = run_grid(
        ["chain", "community"], [256, 512], [2.0],
        tile=DEFAULT_TILE, dense_max=512,
    )
    return _csv(results, rows)


def cli(argv: list[str] | None = None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--sizes", type=int, nargs="+", default=[512, 1024, 4096]
    )
    ap.add_argument(
        "--densities", type=float, nargs="+", default=[0.5, 2.0]
    )
    ap.add_argument(
        "--families",
        nargs="+",
        default=["chain", "community", "power_law"],
        help="sparse families from tests/helpers.py",
    )
    ap.add_argument("--tile", type=int, default=DEFAULT_TILE)
    ap.add_argument(
        "--dense-max",
        type=int,
        default=1024,
        help="largest n given a full dense fixpoint run; above it the "
        "dense time is step-time x iterations (dense_estimated: true)",
    )
    ap.add_argument(
        "--smoke", action="store_true", help="tiny CI config: n=256 only"
    )
    ap.add_argument(
        "--json", default=None, metavar="OUT", help="write JSON payload"
    )
    args = ap.parse_args(argv)
    if args.smoke:
        args.sizes = [256]
        args.densities = [2.0]
        args.families = ["chain", "community"]
        args.dense_max = 256
    results = run_grid(
        args.families, args.sizes, args.densities, args.tile,
        args.dense_max,
    )
    out = {"grammar": GRAMMAR, "tile": args.tile, "results": results}
    print("\n".join(_csv(results, [])))
    if args.json:
        Path(args.json).write_text(json.dumps(out, indent=2) + "\n")
    return out


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    cli()
