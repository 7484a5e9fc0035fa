"""Multi-tenant RPCA serving: the async continuous-batching gateway.

    PYTHONPATH=src python examples/rpca_serving.py

Mixed-size tenants stream decomposition jobs into an ``RPCAGateway``
(DESIGN.md Sec. 16): an asyncio request loop accepts ``submit()`` while
solves are in flight, stages queued planes in a paged column pool
(page-span width classes instead of worst-case padding), schedules
admissions across per-method lanes with priority + weighted fairness,
and sheds load with the typed ``QueueFull`` backpressure signal once
the queue is full.  A snapshot hook prints live metrics -- queue depth,
per-lane occupancy, padding-waste ratio, p50/p99 latency -- while the
batch runs.

The gateway rides the ``repro.rpca`` solver registry, so the solver is
a *per-request* choice: most tenants take the factorized ``cf`` lane,
one latency-insensitive tenant asks for the exact convex ``ialm``
baseline, and a priority-1 tenant jumps the admission queue.  One
tenant then streams an updated matrix and warm-starts from its prior
factors.  The slot-table ``RPCAService`` underneath remains available
directly for synchronous callers (final snippet).
"""
import asyncio
import time

import jax
import numpy as np

from repro.core import DCFConfig, QueueFull, generate_problem, relative_error
from repro.jax_cache import enable_persistent_cache
from repro.serving.gateway import GatewayConfig, RPCAGateway
from repro.serving.rpca_service import RPCAService, RPCAServiceConfig


def snapshot(mets):
    occ = {k: v["occupied"] for k, v in mets["lanes"].items()}
    lat = mets["latency"]
    print(f"  [tick {mets['ticks']:3d}] queue={mets['queue_depth']} "
          f"in_flight={mets['in_flight']} lanes={occ} "
          f"waste={mets['padding']['waste_ratio']:.2f}x "
          f"homog-vs-paged={mets['padding']['homogeneous_ratio']:.2f}x "
          f"p50={lat['p50_ms']:.0f}ms p99={lat['p99_ms']:.0f}ms")


async def serve():
    m, n, rank = 200, 200, 10
    # Mixed-width tenants: narrow ones pay their page span (here n/4 =
    # 50 columns per page), not the full 200-column worst case.
    widths = [50, 50, 100, 100, 150, 200, 200, 200, 200, 200]
    tenants = [
        generate_problem(jax.random.PRNGKey(i), m, w, rank, 0.05)
        for i, w in enumerate(widths)
    ]

    gcfg = GatewayConfig(
        page_cols=50, pool_pages=64, max_queue=8, slots=4,
        rounds_per_tick=10, max_rounds=150, tol=5e-4,
        lane_weights=(("cf", 2.0), ("ialm", 1.0)),  # cf admits 2:1
        snapshot_every=5,
    )
    async with RPCAGateway(m, n, DCFConfig.tuned(rank), gcfg,
                           snapshot_hook=snapshot) as gw:
        t0 = time.perf_counter()
        tickets = []
        for i, ten in enumerate(tenants):
            while True:
                try:
                    tickets.append(await gw.submit(
                        ten.m_obs,
                        method="ialm" if i == 7 else None,
                        priority=1 if i == 9 else 0,  # tenant 9 jumps the queue
                    ))
                    break
                except QueueFull:
                    # Typed backpressure: the queue is at max_queue while
                    # solves are in flight -- yield and retry.
                    await asyncio.sleep(0.01)
        resps = [await t for t in tickets]
        dt = time.perf_counter() - t0

        for i, (ten, r) in enumerate(zip(tenants, resps)):
            err = float(relative_error(r.l, r.s, ten.l0, ten.s0))
            pri = " (priority)" if i == 9 else ""
            print(f"tenant {i}: {r.method:4s} {r.rounds:3d} rounds, "
                  f"{np.asarray(r.l).shape[1]:3d} cols, err {err:.2e}{pri}")
        print(f"{len(tenants)} tenants through {gcfg.slots} slots in "
              f"{dt:.2f}s ({len(tenants) / dt:.1f} problems/s, incl. "
              f"compile)")

        mets = gw.metrics()
        print(f"admitted={mets['admitted']} completed={mets['completed']} "
              f"shed={mets['shed']} "
              f"p50={mets['latency']['p50_ms']:.0f}ms "
              f"p99={mets['latency']['p99_ms']:.0f}ms")
        # The priority-1 tenant admitted ahead of its FIFO position.
        order = gw.admissions
        print(f"admission order: {order} "
              f"(tenant 9 admitted #{order.index(tickets[9].id) + 1})")

        # Streaming refresh: tenant 0's data drifts; warm-start from its
        # prior factors through the same gateway.
        drifted = tenants[0].m_obs + 0.01 * jax.random.normal(
            jax.random.PRNGKey(99), tenants[0].m_obs.shape)
        refresh = await (await gw.submit(
            drifted, warm=(resps[0].u, resps[0].v)))
        print(f"tenant 0 warm refresh: {refresh.rounds} rounds "
              f"(cold took {resps[0].rounds})")


def legacy_service():
    """The synchronous slot table underneath, driven directly -- for
    callers that own their own loop and want submit/tick/poll control."""
    m = n = 120
    rank = 6
    p = generate_problem(jax.random.PRNGKey(5), m, n, rank, 0.05)
    svc = RPCAService(m, n, DCFConfig.tuned(rank),
                      RPCAServiceConfig(slots=2, rounds_per_tick=10))
    slot = svc.try_submit(p.m_obs)
    while svc.pending():
        svc.tick()
    resp = svc.poll(slot)
    svc.release(slot)
    err = float(relative_error(resp.l, resp.s, p.l0, p.s0))
    print(f"direct RPCAService: {resp.rounds} rounds, err {err:.2e}")


def main():
    asyncio.run(serve())
    legacy_service()


if __name__ == "__main__":
    enable_persistent_cache()
    main()
