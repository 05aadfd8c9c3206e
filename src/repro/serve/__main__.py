"""Serve CLI: ``python -m repro.serve`` starts the session server.

Modes::

    PYTHONPATH=src python -m repro.serve --port 8642
        Serve until interrupted (SIGINT) or POST /shutdown.

    PYTHONPATH=src python -m repro.serve --smoke examples/scenarios/x.json
        Self-contained lifecycle check (the CI tier-1 gate): bind an
        ephemeral port, create a session from the scenario, stream one
        chunk over HTTP, suspend, resume, run again, assert the compile
        cache shows shared compilation, shut down cleanly.  Exit 0 on
        success, non-zero with a message on any failure.
"""
from __future__ import annotations

import argparse
import sys

from repro.launch.runtime import setup_jax


def _smoke(scenario: str, warm_ms: float | None) -> int:
    from repro.serve.http import ServeClient, SimServer
    from repro.serve.session import SessionManager

    server = SimServer(SessionManager(warm_ms=warm_ms), port=0).start()
    print(f"smoke: serving on {server.url}")
    try:
        client = ServeClient(server.url, timeout=300.0)
        assert client.healthz()["ok"], "healthz failed"

        sid = client.create(scenario_path=scenario)["id"]
        print(f"smoke: created session {sid}")

        records = client.run(sid, t_ms=100.0, chunk_ms=50.0)
        chunks = [r for r in records if "chunk" in r]
        final = records[-1]
        assert len(chunks) >= 1, f"expected streamed chunks, got {records}"
        assert final.get("done"), f"missing final summary: {records}"
        print(f"smoke: streamed {len(chunks)} chunks, "
              f"rtf={final['rtf']:.3f}")

        ckpt = client.suspend(sid)["checkpoint"]
        info = next(s for s in client.sessions() if s["id"] == sid)
        assert info["status"] == "suspended", info
        print(f"smoke: suspended -> {ckpt}")

        client.resume(sid)
        records = client.run(sid, t_ms=50.0)
        assert records[-1].get("done"), records
        print("smoke: resumed and ran again")

        # a second session from the same scenario must not recompile
        stats0 = client.stats()
        sid2 = client.create(scenario_path=scenario)["id"]
        client.run(sid2, t_ms=50.0)
        stats1 = client.stats()
        before = stats0["compile_caches"]["compiles"]
        after = stats1["compile_caches"]["compiles"]
        assert after == before, \
            f"second same-scenario session recompiled: {before} -> {after}"
        print(f"smoke: second session shared all {after} compilations")

        client.destroy(sid)
        client.destroy(sid2)
        client.shutdown()
        print("smoke: ok")
        return 0
    finally:
        server.stop()


def main(argv=None) -> int:
    setup_jax()
    ap = argparse.ArgumentParser(
        description="repro session server (stdlib HTTP/JSON front end)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8642,
                    help="0 binds an ephemeral port")
    ap.add_argument("--root", default=None,
                    help="checkpoint root for suspended sessions "
                         "(default: a temp directory)")
    ap.add_argument("--max-backends", type=int, default=8)
    ap.add_argument("--warm-ms", type=float, default=None,
                    help="warm up each new session's executable for this "
                         "horizon at create time")
    ap.add_argument("--smoke", metavar="SCENARIO", default=None,
                    help="run the self-contained lifecycle check against "
                         "this scenario JSON and exit")
    args = ap.parse_args(argv)

    if args.smoke is not None:
        return _smoke(args.smoke, args.warm_ms)

    from repro.serve.http import SimServer
    from repro.serve.session import SessionManager

    manager = SessionManager(root=args.root,
                             max_backends=args.max_backends,
                             warm_ms=args.warm_ms)
    server = SimServer(manager, host=args.host, port=args.port,
                       quiet=False)
    print(f"serving on {server.url} (POST /shutdown or Ctrl-C to stop)")
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
