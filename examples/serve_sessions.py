"""Session-server walkthrough: many users, one compiled microcircuit.

Replaces the seed's LM ``serve_decode.py``: the serving workload here is
*simulation sessions* — each user holds a live microcircuit with private
dynamical state, while every same-scenario session shares one built
backend and one compilation per distinct program (``repro.serve``).

Two modes::

    PYTHONPATH=src python examples/serve_sessions.py
        In-process: drives a SessionManager directly — create seeded
        replicas, run them coalesced through the vmapped batch path,
        suspend one to disk, resume it, print the compile-cache counters.

    PYTHONPATH=src python examples/serve_sessions.py --http
        Same lifecycle over the stdlib HTTP/JSON front end (an ephemeral
        local SimServer + ServeClient), streaming per-chunk snapshots.
"""
from __future__ import annotations

import argparse

from repro.launch.runtime import setup_jax

SCENARIO = "examples/scenarios/smoke_background.json"


def in_process(scenario: str) -> None:
    from repro.serve import SessionManager

    with SessionManager() as mgr:
        # three users, one scenario: seeded replicas share the backend,
        # so only the first create pays for build + compile
        sessions = [mgr.create(scenario, seed=100 + i) for i in range(3)]
        ids = [s.id for s in sessions]
        print("sessions:", ids)

        # coalesced: one vmapped device program for the whole group,
        # bitwise-equal to running each session alone
        results = mgr.run_many({sid: 200.0 for sid in ids})
        for sid in ids:
            r = results[sid]
            spikes = int(r.data["pop_counts"].sum())
            print(f"  {sid}: {spikes} spikes, rtf={r.rtf:.1f}")

        # park one user: checkpoint to disk, free its device state
        mgr.suspend(ids[0])
        print("suspended:", ids[0],
              "->", mgr.get(ids[0]).ckpt_dir)
        mgr.resume(ids[0])
        r = mgr.run(ids[0], 100.0)
        print("resumed:", ids[0], f"rtf={r.rtf:.1f}")

        stats = mgr.stats()
        print("backend pool:", stats["backend_pool"])
        print("total compilations:", stats["compile_caches"]["compiles"])


def over_http(scenario: str) -> None:
    from repro.serve import ServeClient, SimServer

    server = SimServer(port=0).start()
    print("serving on", server.url)
    try:
        client = ServeClient(server.url)
        ids = [client.create(scenario_path=scenario, seed=100 + i)["id"]
               for i in range(2)]
        print("sessions:", ids)

        # streamed run: one NDJSON record per 100 ms chunk
        for rec in client.run(ids[0], t_ms=300.0, chunk_ms=100.0):
            if "chunk" in rec:
                print(f"  chunk {rec['chunk']}: "
                      f"t={rec['t_model_ms']:.0f} ms rtf={rec['rtf']:.1f} "
                      f"pop_spikes={rec.get('pop_spikes')}")
            elif rec.get("done"):
                print(f"  done: session at "
                      f"{rec['session_t_model_ms']:.0f} ms model time")

        print("suspend/resume:", client.suspend(ids[0])["checkpoint"])
        client.resume(ids[0])
        client.run_many({sid: 100.0 for sid in ids})
        print("stats:", client.stats()["compile_caches"]["totals"])
        client.shutdown()
    finally:
        server.stop()


def main() -> None:
    setup_jax()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scenario", default=SCENARIO)
    ap.add_argument("--http", action="store_true",
                    help="run the lifecycle over the HTTP front end")
    args = ap.parse_args()
    if args.http:
        over_http(args.scenario)
    else:
        in_process(args.scenario)


if __name__ == "__main__":
    main()
