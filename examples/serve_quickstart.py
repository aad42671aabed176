"""Solve-as-a-service quickstart: run the §20 serving stack in-process.

The paper's architecture ultimately *serves* imaging workloads to many
clients at once.  ``repro.serve`` is that frontend: an asyncio core
that admits requests, coalesces compatible ones (same workload, config
and run options; shapes grouped by the §19 planner) into one
``solve_many`` dispatch per micro-batch, plus a stdlib-only
JSON-over-HTTP transport.

This example starts the HTTP server on a loopback port, fires a small
mixed-shape population at it from ``ServeClient``, streams one
request's per-chunk progress, and prints the service metrics —
including batch occupancy, the signal that coalescing actually
happened.

    PYTHONPATH=src python examples/serve_quickstart.py

Resilient requests ride the same wire: pass ``options={"resilience":
{"max_retries": 2}}`` (and, for drills, ``chaos="dispatch@2"`` — chaos
requests always dispatch solo) and the JSON result carries the
RecoveryReport ledger.

Ops runbook (§21) — what to do when serving misbehaves:

- **Is it alive? Is it ready?**  ``GET /v1/healthz`` is liveness: it
  stays ``ok`` while draining and only flips after a crash.  ``GET
  /v1/readyz`` is readiness: 503 with a detail dict while draining,
  crashed, queue-full, or any workload circuit breaker is open — point
  load balancers here, not at healthz.
- **A workload keeps failing.**  ``/v1/metrics`` shows per-workload
  breaker states (``breakers``) and the ``shed`` counter.  An open
  breaker rejects that workload's submits with ``retriable: true``
  (clients should back off and resubmit); after the cooldown one probe
  request decides whether it closes again.  Other workloads are
  unaffected.
- **A whole coalesced batch failed.**  With ``quarantine`` on
  (default) the service re-dispatches each member solo — look for the
  ``quarantined`` counter and the per-request ``recovery`` report in
  the failed request's result: only the genuinely poisoned request
  fails.
- **Requests hang.**  Set ``dispatch_timeout_s``; the watchdog fails
  hung dispatches (``hung`` counter, ``"hung dispatch"`` error) and
  feeds the breaker.
- **The process died.**  Run with ``journal_dir=`` (and, for long
  solves, ``checkpoint_dir=`` + ``checkpoint_every=``).  Start a new
  service over the SAME ``journal_dir``: every admitted-but-unfinished
  request is re-admitted under its original id (``replayed: true`` in
  its status), journaled buckets re-dispatch together and resume from
  their per-bucket checkpoints.  Clients keep polling the same request
  ids — ``restart_and_replay()`` below drills exactly this.
"""
import tempfile

import jax
import numpy as np

from repro.imaging import psf as psf_op
from repro.launch.cache import enable_compile_cache
from repro.serve import ServeConfig
from repro.serve.client import ServeClient
from repro.serve.server import serve_http

CFG = dict(mode="sparse", max_iter=12, tol=0.0, n_scales=2)
OPTIONS = dict(chunk=4, cost_every=1)


def main():
    # 0.2 s coalescing window, up to 8 requests per dispatched bucket
    with serve_http(ServeConfig(batch_window_s=0.2, max_batch=8)) as h:
        print(f"serving on {h.url}")
        client = ServeClient(h.url, timeout=600)

        # a mixed population: two stamp shapes -> two coalesced
        # buckets.  Simulate up front: the submits must land within one
        # coalescing window of each other for the scheduler to group
        # them (real clients arrive concurrently; this loop is serial).
        population = [
            psf_op.simulate(n, jax.random.PRNGKey(i), stamp=stamp)
            for i, (n, stamp) in enumerate([(3, 16), (5, 16),
                                            (4, 20), (6, 20)])]
        ids = [client.submit(
            "deconvolve", (np.asarray(d.Y), np.asarray(d.psfs)),
            cfg=CFG, options=OPTIONS) for d in population]
        print(f"submitted {len(ids)} requests")

        # stream one request's chunk-boundary progress while it runs
        for event in client.events(ids[0]):
            if event.get("kind") == "chunk":
                print(f"  [{ids[0]}] iter {event['done']:3d}  "
                      f"cost={event['cost']:.5f}")
            else:
                print(f"  [{ids[0]}] {event['status']}")

        for rid in ids:
            res = client.result(rid, timeout=600)
            print(f"{rid}: {res['status']}  batch={res['batch_size']}  "
                  f"bucket={res['bucket_key']}  "
                  f"final_cost={res['costs'][-1]:.5f}  "
                  f"p99_chunk={res['time_percentiles_s']['p99']:.4f}s")

        m = client.metrics()
        occ = m["batch_occupancy"]
        print(f"served {m['counters']['completed']} requests, "
              f"occupancy mean={occ['mean']:.1f} max={occ['max']}, "
              f"p50 latency={m['latency_s'].get('p50', 0):.2f}s")

        # readiness flips during drain; liveness does not (§21 runbook)
        print(f"readyz before drain: {client.ready()['ready']}")
        client.drain()
        print(f"healthz after drain: ok={client.health()['ok']} "
              f"readyz: {client.ready()['ready']}")


def restart_and_replay():
    """The §21 restart drill, scripted: a journaled service crashes
    with an admitted request it never ran; a second service started
    over the same ``journal_dir`` owes it, replays it, and finishes it
    under the original request id."""
    journal_dir = tempfile.mkdtemp(prefix="serve-journal-")
    d = psf_op.simulate(3, jax.random.PRNGKey(0), stamp=16)
    inputs = (np.asarray(d.Y), np.asarray(d.psfs))

    # --- incident: the service journals the admit, then "crashes"
    # before the scheduler ever sees the request (serve_admit_drop is
    # the §21 chaos point for exactly that window)
    cfg = ServeConfig(batch_window_s=0.1, max_batch=8,
                      journal_dir=journal_dir,
                      chaos_spec="serve_admit_drop@0")
    with serve_http(cfg) as h:
        client = ServeClient(h.url, timeout=600)
        rid = client.submit("deconvolve", inputs, cfg=CFG,
                            options=OPTIONS)
        print(f"[incident] admitted {rid}, then the process dies")
        h.runner.call(h.runner.service.abandon())
        print(f"[incident] healthz now ok="
              f"{client.health()['ok']}")

    # --- recovery: same journal_dir, fresh process — the request is
    # re-admitted under its original id and completes
    with serve_http(ServeConfig(batch_window_s=0.1, max_batch=8,
                                journal_dir=journal_dir)) as h:
        client = ServeClient(h.url, timeout=600)
        res = client.result(rid, timeout=600)
        print(f"[recovery] {rid}: {res['status']} "
              f"(replayed={res['replayed']}) "
              f"final_cost={res['costs'][-1]:.5f}")
        assert res["status"] == "done" and res["replayed"]
        client.drain()


if __name__ == "__main__":
    enable_compile_cache()
    main()
    restart_and_replay()
