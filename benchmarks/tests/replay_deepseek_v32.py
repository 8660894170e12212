"""What a seed does to `deepseek-v3.2-serve-sparse-long-context`'s window,
without the chip: the closed loop's schedule replayed from the plan alone,
and a window's tokens per second from four fitted costs.

At this cell's chunk (1,024 rows: one launch a prefilling slot a step,
`engine.launch_multiples`) what each engine step computes is a function of
the plan and of nothing the clock says: `replay` gives, a step, the depth
of every chunk launch, the decode batch and the tokens the program's
counters will show.  `--readings` holds that against a run's
`readings-*.json` step by step.  The device is busy 95-98% of the time, so
a step ends when the work dispatched one step before it is done, and the
work is `WORK`'s: fitted on the three 250 s runs of PR 58's review round
(sums of 25 steps, least squares), it read that round's eleven untraced
50 s runs within 0.6% each (PERF.md s6).  A time read here is the fit's,
never a measurement: it says how far SEEDS move the window, which is what
`strata` and `ramp_s` of the traffic file were chosen by.

    python3 benchmarks/tests/replay_deepseek_v32.py --seeds 300
    python3 benchmarks/tests/replay_deepseek_v32.py --strata 4 --ramp 48
    python3 benchmarks/tests/replay_deepseek_v32.py \
        --readings benchmarks/out/<cell>/readings-seed<n>-trace0.json
"""
from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from benchmarks import traffic  # noqa: E402

TRAFFIC = "sparse-long-context-closed"
CONFIG = "deepseek-v3.2-ep32-depth5"
#: seconds: a chunk launch, a 1,024 positions of its depth, their square,
#: a decode pass (my chip runs, PR 58: three runs of 250 s, 7,101 steps)
WORK = {"launch": 50.502e-3, "depth": 3.419e-3, "depth2": 0.080e-3,
        "decode": 18.516e-3}


def plan_lengths(tf: dict, seed: int, vocab: int, count: int) -> list:
    """(prompt, answer) lengths of `traffic.plan_requests`'s first `count`
    requests, from the same draws."""
    plan = traffic.plan_requests(tf, seed, vocab, count=count)
    return [(len(p.prompt), p.max_new) for p in plan]


def replay(plan: list, steps: int, *, slots: int, chunk: int,
           outstanding: int):
    """The loop of `benchmarks/run.py` over `ServingEngine.step`, a step a
    row: (depths of the chunk launches, decode batch, prompt rows, tokens
    out); and a request a row: [step it was sent before, step it ended
    in]."""
    table = [None] * slots
    queue, rows, requests = [], [], []
    nxt = in_engine = admitted = 0
    for s in range(steps):
        while nxt < len(plan) and in_engine < outstanding:
            queue.append((len(requests),) + plan[nxt])
            requests.append([s, None])
            nxt += 1
            in_engine += 1
        for i in range(slots):
            if table[i] is None and queue:
                rid, p, o = queue.pop(0)
                table[i] = dict(rid=rid, p=p, o=o, rows=0, out=0, flight=0,
                                seq=admitted)
                admitted += 1
        depths, ends, prompt_rows, tokens = [], [], 0, 0
        for st in sorted((st for st in table if st and st["rows"] < st["p"]),
                         key=lambda st: st["seq"]):
            n = min(chunk, st["p"] - st["rows"])
            depths.append(st["rows"])
            st["rows"] += n
            prompt_rows += n
            if st["rows"] == st["p"]:
                ends.append(st)
        batch = [st for st in table if st and st["out"]
                 and st["out"] + st["flight"] < st["o"]]
        for st in table:            # the step before's decode lands
            if st and st["flight"]:
                st["out"] += 1
                st["flight"] = 0
                tokens += 1
        for st in batch:
            st["flight"] = 1
        for st in ends:             # a prompt's first token
            st["out"] = 1
            tokens += 1
        for i, st in enumerate(table):
            if st and st["out"] >= st["o"]:
                requests[st["rid"]][1] = s
                table[i] = None
                in_engine -= 1
        rows.append((depths, len(batch), prompt_rows, tokens))
    return rows, requests


def step_ends(rows: list) -> np.ndarray:
    """When each step ends, from the loop's start: when the work
    dispatched by the step before it is done."""
    work = []
    for depths, batch, _, _ in rows:
        d = np.asarray(depths, np.float64) / 1024.0
        work.append(WORK["launch"] * len(depths) + WORK["depth"] * d.sum()
                    + WORK["depth2"] * (d ** 2).sum()
                    + (WORK["decode"] if batch else 0.0))
    return np.concatenate([[0.05], np.cumsum(work)[:-1] + 0.05])


def window(rows, requests, ramp_s: float, seconds: float) -> dict:
    """`run.py`'s `serve_tokens_per_s` of the window [ramp_s, ramp_s +
    seconds) of the loop, and its requests both due and done in it."""
    t = step_ends(rows) - ramp_s
    inside = (t >= 0) & (t < seconds)
    tokens = np.asarray([r[2] + r[3] for r in rows])
    both = sorted(t[end] for sent, end in requests if end is not None
                  and 0 <= (t[sent - 1] if sent else -ramp_s) < seconds
                  and 0 <= t[end] < seconds)
    return {"rate": float(tokens[inside].sum()
                          / (t[inside][-1] - t[t < 0][-1])),
            "due_and_done": len(both),
            "first_such_done_s": float(both[0]) if both else None}


def quartile_spread(values) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def cell(strata=None):
    """(the traffic file, the configuration, `replay`'s shape)."""
    tf = traffic.load_traffic(TRAFFIC)
    cfg = traffic.load_json("configs", CONFIG)
    if strata:
        tf["strata"] = strata
    sv = cfg["serving"]
    return tf, cfg, dict(slots=sv["num_slots"], chunk=sv["prefill_chunk"],
                         outstanding=tf["outstanding"])


def loop(seed: int, steps: int, until_s: float = 0.0, strata=None):
    """`steps` steps of the loop under `seed`, doubled until they pass
    `until_s`; a request lives 256 steps or more, 16 at a time."""
    tf, cfg, shape = cell(strata)
    while True:
        plan = plan_lengths(tf, seed, cfg["vocab_size"], 32 + steps // 12)
        rows, requests = replay(plan, steps, **shape)
        if step_ends(rows)[-1] >= until_s:
            return rows, requests
        steps *= 2


def follows(seed: int, seen: list, strata=None) -> bool:
    """Whether the (prompt rows, tokens out) a run's steps showed, `seen`,
    are a stretch of the replayed loop's."""
    rows, _ = loop(seed, len(seen) + 2048, strata=strata)
    mine = [(r[2], r[3]) for r in rows]
    seen = [tuple(s) for s in seen]
    return any(mine[k: k + len(seen)] == seen
               for k in range(len(mine) - len(seen) + 1)
               if mine[k: k + 8] == seen[:8])


def windows(seeds, ramp_s: float, seconds: float = 50.0, strata=None):
    """`window` of each seed's loop."""
    until = ramp_s + seconds
    return [dict(window(*loop(seed, math.ceil(until / 0.06), until, strata),
                        ramp_s, seconds), seed=seed) for seed in seeds]


def drawn_seeds(n: int) -> list:
    """n seeds as the driver's are: up to a little over 2**31."""
    return [int(s) for s in np.random.default_rng(58).integers(
        0, 2 ** 31 + 1000, size=n)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--strata", type=int)
    ap.add_argument("--ramp", type=float)
    ap.add_argument("--seeds", type=int, default=60,
                    help="how many seeds to draw")
    ap.add_argument("--seed", type=int, action="append",
                    help="these seeds and no drawn ones")
    ap.add_argument("--readings", help="a run's readings file: hold the "
                    "replay against its steps")
    args = ap.parse_args(argv)
    if args.readings:
        with open(args.readings) as f:
            read = json.load(f)
        ok = follows(read["seed"], [s[1:3] for s in read["steps"]],
                     args.strata)
        print(json.dumps({"seed": read["seed"], "steps": len(read["steps"]),
                          "replayed_alike": ok}))
        return 0 if ok else 1
    tf = cell(args.strata)[0]
    ramp = tf["ramp_s"] if args.ramp is None else args.ramp
    got = windows(args.seed or drawn_seeds(args.seeds), ramp,
                  strata=args.strata)
    if args.seed:
        for g in got:
            print(json.dumps(g))
    rates = [g["rate"] for g in got]
    out = {"strata": tf["strata"], "ramp_s": ramp, "seeds": len(got),
           "median": statistics.median(rates),
           "min": min(rates), "max": max(rates),
           "due_and_done_min": min(g["due_and_done"] for g in got),
           "first_such_done_s_max": max(
               (g["first_such_done_s"] or math.inf) for g in got)}
    if len(rates) >= 4:
        out["quartile_spread_pct"] = 100 * quartile_spread(rates)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
