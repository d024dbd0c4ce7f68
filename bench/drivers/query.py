"""Open-loop window queries through the decode service: the analyst.

Set-up fits and compresses one container, registers it once in a
``DecodeService(max_batch=...)`` and warms every species-union size a
tick can form. The window then sends queries at the times a seeded
schedule fixes (:func:`schedule`), whatever the service does: Poisson
arrivals at the mix's rate; species drawn Zipf over the species with
ranks shuffled per seed, one species or a few; a frame range of 1 to 4
frames inside the container. Each query is timed from its scheduled
send time to its result. After the last send the window waits, at most
``drain_s`` seconds, for the answers still due.

End to end: ``query_p95_ms``, the 95th percentile of all queries'
latencies (a query that failed or never came counts as failed).

Check: a sample of the answers drawn from the seed, each held to the
block bound against the same slice of the original field; and no query
failed.
"""

from __future__ import annotations

import threading
import time

import numpy as np

BLOB_ID = "field"


def schedule(traffic: dict, n_species: int, n_time: int, seconds: float,
             seed: int) -> list[tuple[float, object, tuple[int, int]]]:
    """The window's queries: (send time in s, species, (t0, t1)).

    A pure function of the mix, the sizes and the seed: send times never
    depend on how fast answers come. Every seed sends the same load in
    another order: ``round(rate * seconds)`` queries whose gaps are the
    quantiles of the exponential law at the mix's rate (Poisson
    arrivals), whose species counts and frame counts are the mix's
    shares, each list shuffled by the seed. Which species a query asks
    for is drawn Zipf over the species, with ranks shuffled by the seed.
    """
    rng = np.random.default_rng([int(seed), 0x9E77])
    rate = float(traffic["rate_per_s"])
    n = int(round(rate * seconds))
    if n == 0:
        return []
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q))
    # the last send falls inside the window
    times = np.cumsum(gaps) * (seconds * n / (n + 1)) / gaps.sum()
    lo, hi = traffic["multi_species"]
    n_single = int(round(float(traffic["single_share"]) * n))
    counts = rng.permutation(
        [1] * n_single + [lo + i % (hi - lo + 1) for i in range(n - n_single)])
    fmin, fmax = traffic["frames"]
    fmax = min(fmax, n_time)
    lengths = rng.permutation([fmin + i % (fmax - fmin + 1)
                               for i in range(n)])
    ranks = rng.permutation(n_species)
    w = 1.0 / np.arange(1, n_species + 1) ** float(traffic["zipf_a"])
    w /= w.sum()
    out = []
    for t, k, length in zip(times, counts, lengths):
        picks = rng.choice(n_species, size=int(k), replace=False, p=w)
        species = (int(ranks[picks[0]]) if k == 1
                   else [int(ranks[p]) for p in picks])
        t0 = int(rng.integers(0, n_time - length + 1))
        out.append((float(t), species, (t0, t0 + int(length))))
    return out


def setup(ctx):
    import jax.numpy as jnp

    from repro.serve import DecodeService

    from bench import harness

    decode = harness.load_module(harness.BENCH / "drivers" / "decode.py")
    blob = decode.make_container(ctx)
    s, t = ctx.field.shape[:2]
    svc = DecodeService(max_batch=int(ctx.traffic["max_batch"]))
    svc.start()
    svc.register(BLOB_ID, blob)
    # a tick replays the union of its requests' species, so every union
    # size from 1 to S can occur; each request then takes its own
    # species (at most the mix's largest) from the union
    most = int(ctx.traffic["multi_species"][1])
    with ctx.span("warm"):
        svc.decode(BLOB_ID, species=0, time_range=(0, t))
        for k in range(1, s + 1):
            svc.decode(BLOB_ID, species=list(range(k)), time_range=(0, t))
        nb, d = ctx.shapes.n_blocks, ctx.shapes.voxels
        x = jnp.zeros((s, nb, d), jnp.float32)
        for k in range(1, s + 1):
            y = x[np.arange(k)]
            for m in range(1, min(k, most) + 1):
                y[np.arange(m)].block_until_ready()
    ctx.spans.clear()
    plan = schedule(ctx.traffic, s, t, ctx.seconds, ctx.seed)
    n = len(plan)
    k = min(int(ctx.traffic["check_sample"]), n)
    sample = set(np.random.default_rng([int(ctx.seed), 0xC4EC]).choice(
        n, size=k, replace=False).tolist()) if n else set()
    ctx.note(f"{n} queries scheduled at {ctx.traffic['rate_per_s']}/s "
             f"over {ctx.seconds} s; {k} sampled for the check")
    return {"svc": svc, "plan": plan, "sample": sample}


def window(ctx, state):
    svc, plan = state["svc"], state["plan"]
    stats0 = svc.stats.as_dict()
    n = len(plan)
    done = [None] * n
    late = [0.0] * n
    futs = []
    all_done = threading.Event()
    left = [n]
    lock = threading.Lock()

    def finished(i):
        def cb(_):
            done[i] = time.perf_counter()
            with lock:
                left[0] -= 1
                if left[0] == 0:
                    all_done.set()
        return cb

    if n == 0:
        all_done.set()
    t0 = time.perf_counter()
    with ctx.span("send"):
        for i, (ts, species, tr) in enumerate(plan):
            delay = t0 + ts - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            late[i] = time.perf_counter() - (t0 + ts)
            fut = svc.submit(BLOB_ID, species=species, time_range=tr)
            futs.append(fut)
            fut.add_done_callback(finished(i))
    with ctx.span("drain"):
        all_done.wait(timeout=max(0.0, t0 + ctx.seconds
                                  + float(ctx.traffic["drain_s"])
                                  - time.perf_counter()))
    lat_ms = []
    failed = 0
    answers = {}
    for i, fut in enumerate(futs):
        if done[i] is None or fut.exception() is not None:
            failed += 1
            continue
        lat_ms.append((done[i] - (t0 + plan[i][0])) * 1e3)
        if i in state["sample"]:
            answers[i] = fut.result()
    stats = svc.stats.as_dict()
    ctx.counters = {k: stats[k] - stats0[k] for k in stats}
    ctx.units = len(lat_ms)
    close = t0 + ctx.seconds
    state["answers"] = answers
    state["failed"] = failed
    state["late"] = late
    state["lat_ms"] = lat_ms
    state["backlog_at_close"] = sum(1 for d in done
                                    if d is None or d > close)
    if late:
        ctx.note(f"generator lateness: max {max(late) * 1e3:.3f} ms, "
                 f"p95 {np.percentile(late, 95) * 1e3:.3f} ms")
    ctx.note(f"service counters in the window: {ctx.counters}; "
             f"{state['backlog_at_close']} unanswered at the window's "
             f"close")
    metrics = {}
    if lat_ms:
        metrics["query_p95_ms"] = float(np.percentile(lat_ms, 95))
        ctx.note(f"latency ms: p50 {np.percentile(lat_ms, 50):.3f} p90 "
                 f"{np.percentile(lat_ms, 90):.3f} p95 "
                 f"{metrics['query_p95_ms']:.3f} max {max(lat_ms):.3f}, "
                 f"{len(lat_ms)} answered")
    return {"attempted": n, "failed": failed, "metrics": metrics}


def check(ctx, state, result):
    from bench import reference

    state["svc"].stop()
    limits = ctx.config["limits"]
    target = float(ctx.config["target_nrmse"])
    mn, rng = reference.species_scale(ctx.field)
    answers = state.pop("answers")
    # no answer to compare is no evidence: it cannot pass
    worst = 0.0 if answers else float("inf")
    for i, answer in sorted(answers.items()):
        _, species, (t0, t1) = state["plan"][i]
        idx = [species] if isinstance(species, int) else list(species)
        got = answer[None] if isinstance(species, int) else answer
        if ctx.control:
            got = reference.bf16_control(got, mn[idx], rng[idx])
        r = reference.guarantee_readings(
            ctx.field[idx, t0:t1], got, target=target,
            block=ctx.shapes.block, mn=mn[idx], rng=rng[idx], frame0=t0)
        worst = max(worst, r["block"])
    return {
        "block": reference.make_check(worst, limits["block"]),
        "failed_queries": reference.make_check(state["failed"], 0),
    }
