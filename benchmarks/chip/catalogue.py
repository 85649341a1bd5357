"""Build the question pools of a certified traffic mix.

    python3 benchmarks/chip/catalogue.py \\
        benchmarks/chip/traffic/paper_ci_mix.json \\
        benchmarks/chip/configs/paper_divisible_p256.json \\
        --block 1:2 2:11 3:7 --pool 128

A certified question replicates until its confidence interval meets the
target, so how much work it costs depends on its seed. A run that drew
fresh seeds would measure a different amount of work each time. The traffic
file instead holds pools of question seeds, one for each number of rounds a
question takes, and a block: how many questions of each pool a run asks at
a time (``--block rounds:count``). A run asks whole blocks, each in an order
drawn from its own seed (``cell.blocks``), so every run asks for the same
work in another order. The block follows the natural mix, which the scan
records as ``candidates_by_rounds``.

Candidates are a fixed sequence, scanned in order: the first that takes one
round is the warm-up (``warm_seed0``), so set-up does the same work on every
seed; each later one joins its rounds' pool until every pool holds
``--pool`` seeds. The rounds of each come from the reference, on the host,
so the file is rebuilt exactly. Rewrites the traffic file in place.
"""
from __future__ import annotations

import argparse
import json
import multiprocessing
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import cell as cellmod  # noqa: E402
import reference as ref  # noqa: E402

CHUNK = 64


def rounds_of(args) -> int:
    config, traffic, seed0 = args
    model = cellmod.task_model(config)
    batch, ms = traffic["batch_reps"], []
    for stream in range(-(-traffic["max_reps"] // batch)):
        for row in ref.query_rows(traffic["W_list"], traffic["lam_list"],
                                  batch, seed0, stream,
                                  [tuple(t) for t in config["theta"]]):
            ms.append(model.simulate(config, row)["makespan"])
        r = ref.stop_round(ms, batch, traffic["ci"], traffic["ci_relative"],
                           traffic["min_reps"], traffic["max_reps"])
        if r > 0:
            return r
    raise AssertionError("unreachable: max_reps always stops")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("traffic", type=Path)
    ap.add_argument("config", type=Path)
    ap.add_argument("--block", nargs="+", required=True,
                    help="rounds:count, one for each pool")
    ap.add_argument("--pool", type=int, default=128)
    ap.add_argument("--workers", type=int, default=4)
    args = ap.parse_args(argv)
    traffic = json.loads(args.traffic.read_text())
    config = json.loads(args.config.read_text())
    block = {r: int(n) for r, n in (b.split(":") for b in args.block)}
    pools = {r: [] for r in block}
    seen, warm, k = {}, None, 0
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(args.workers) as pool:
        while warm is None or any(len(p) < args.pool for p in pools.values()):
            cands = [cellmod._mix(10**9 + k + j) for j in range(CHUNK)]
            k += CHUNK
            for s, r in zip(cands, pool.map(
                    rounds_of, [(config, traffic, s) for s in cands])):
                r = str(r)
                seen[r] = seen.get(r, 0) + 1
                if warm is None:
                    if r == "1":
                        warm = s
                elif r in pools and len(pools[r]) < args.pool:
                    pools[r].append(s)
    traffic.update(warm_seed0=warm, block=block, pools=pools,
                   candidates_by_rounds=dict(sorted(seen.items())))
    args.traffic.write_text(json.dumps(traffic) + "\n")
    print(json.dumps({"candidates": k, "by_rounds": seen}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
