"""Make a trace fixture: one traced run of a cell, its event lists (as
`chipbench.trace.load` gives them) written to a file, to be trimmed by hand
and given hand-computed answers beside `fixtures/kmeans_refit_v5e.json`.

    python3 -m chipbench.tests.record_trace <cell> <seed> <seconds> <out.json>
"""
import json
import sys

from chipbench import run, trace as tracing


def main(workload: str, seed: str, seconds: str, out: str) -> int:
    bench = run.load_bench()
    cell, config, traffic = run.cell_files(bench, workload)
    reduce, seen = tracing.reduce, []
    tracing.reduce = lambda events: seen.append(events) or reduce(events)
    run.execute(cell, config, traffic, int(seed), float(seconds), True, per_layer=run.cell_metrics(bench, workload))
    with open(out, "w") as f:
        json.dump(seen[0], f)
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
