"""Worker process entrypoint
(reference: python/ray/_private/workers/default_worker.py).

Connects to the node's shm store + GCS, reports readiness to its hostd, and
blocks in the task execution loop.  Exits if its hostd disappears (orphan
protection, reference: raylet death → worker suicide).
"""

from __future__ import annotations

import argparse
import logging
import os
import sys
import threading
import time

# Set by hostd on the child it makes, read (and removed) here: the id of
# the `sched/worker_boot` span the worker's `proc/boot` is a child of.
BOOT_SPAN_ENV = "RAY_TPU_BOOT_SPAN"


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--gcs", required=True)
    parser.add_argument("--hostd", required=True)
    parser.add_argument("--store", required=True)
    parser.add_argument("--node-id", required=True)
    parser.add_argument("--job-id", type=int, default=0)
    args = parser.parse_args()
    logging.basicConfig(level=os.environ.get("RAY_TPU_LOGLEVEL", "INFO"))
    from ray_tpu._private.profiling import start_periodic_profile
    pr = start_periodic_profile("RAY_TPU_BOOT_PROFILE", "boot")
    from ray_tpu.util import events, spans
    events.role = "worker"
    # Boot span: from here through the WorkerReady ack, a child of the
    # `sched/worker_boot` span hostd opened when it made this process (its
    # id rides the environment hostd hands a worker), with the imports,
    # CoreWorker's construction and the ack as children: a creation storm
    # shows up as a wall of long proc/boot spans.  All of it is kept in the
    # start-up record.
    tok_boot = spans.begin("proc", "boot", pin=True, pid=os.getpid(),
                           parent=os.environ.pop(BOOT_SPAN_ENV, None))
    with spans.under(tok_boot):
        with spans.span("proc", "imports", pin=True):
            from ray_tpu._private.core_worker import CoreWorker
            from ray_tpu._private.ids import JobID, NodeID
            from ray_tpu._private.rpc import RpcClient

        with spans.span("proc", "core_worker", pin=True):
            cw = CoreWorker(
                mode="worker",
                gcs_address=args.gcs,
                store_path=args.store,
                node_id=NodeID.from_hex(args.node_id),
                hostd_address=args.hostd,
                job_id=JobID(args.job_id.to_bytes(4, "little")),
            )

    # Tasks call ray_tpu.get/put/remote through the process-global worker.
    from ray_tpu import api
    api._worker = cw

    renv_json = os.environ.get("RAY_TPU_RUNTIME_ENV")
    if renv_json:
        import json

        from ray_tpu._private import runtime_env as renv
        cache_root = os.environ.get(
            "RAY_TPU_RUNTIME_ENV_CACHE", "/tmp/ray_tpu/runtime_env")
        os.makedirs(cache_root, exist_ok=True)
        cw.io.run(renv.setup_in_worker(json.loads(renv_json), cw._kv_call,
                                       cache_root), timeout=120)

    hostd = RpcClient(args.hostd)
    tok_ready = spans.begin("proc", "ready_rpc", pin=True,
                            ctx=(tok_boot.trace_id, tok_boot.sid)
                            if tok_boot is not None else None)
    # Registration retries: during a creation storm (hundreds of workers
    # booting on few cores) the daemon can miss a 10s window; a worker
    # dying here amplifies the storm instead of riding it out.
    last = None
    for attempt in range(4):
        try:
            cw.io.run(hostd.call("NodeManager", "WorkerReady", {
                "pid": os.getpid(),
                "worker_id": cw.worker_id,
                "address": cw.address,
                # Piggybacked so leases/actor records carry the native
                # route — peers skip the per-worker NativePort RPC.
                "native_port": (cw._native_rx.port
                                if cw._native_rx else 0),
            }, timeout=10 * (attempt + 1)))
            break
        except Exception as e:  # noqa: BLE001
            last = e
            time.sleep(0.5 * (attempt + 1))
    else:
        raise RuntimeError(f"WorkerReady never acknowledged: {last}")
    spans.end(tok_ready)
    spans.end(tok_boot)
    if pr is not None:
        pr.disable()
        pr.dump_stats(os.path.join(
            os.environ["RAY_TPU_BOOT_PROFILE"], f"boot-{os.getpid()}.prof"))

    parent = os.getppid()

    def orphan_watch():
        while True:
            if os.getppid() != parent:
                logging.warning("hostd died; worker exiting")
                os._exit(1)
            time.sleep(1.0)

    threading.Thread(target=orphan_watch, daemon=True).start()

    # Graceful SIGTERM (reference: default_worker.py sigterm handler →
    # CoreWorkerProcess graceful exit).  Without this the worker dies
    # mid-task and the owner misreads a deliberate kill as a crash and
    # retries; here the handler reports the deliberate exit to hostd,
    # lets the in-flight task drain within worker_sigterm_grace_s, then
    # exits.  Hostd's _escalate_kill SIGKILLs anything that overstays.
    import signal

    from ray_tpu._private.config import GLOBAL_CONFIG

    def _graceful_exit(signum=None, frame=None):
        # Black box first: the flight-recorder ring is the only record of
        # this worker's decisions once the process is gone.
        events.record("proc", "sigterm")
        events.dump_crash("sigterm")

        def drain():
            try:
                cw.io.run(hostd.call(
                    "NodeManager", "WorkerExiting",
                    {"pid": os.getpid(), "reason": "sigterm"}, timeout=2))
            except Exception:
                pass
            deadline = (time.monotonic()
                        + GLOBAL_CONFIG.worker_sigterm_grace_s)
            while cw._running_tasks and time.monotonic() < deadline:
                time.sleep(0.02)
            os._exit(0 if not cw._running_tasks else 1)
        # Drain on a thread: the signal may land on a frame holding locks
        # the in-flight task needs to finish.
        threading.Thread(target=drain, daemon=True).start()

    try:
        signal.signal(signal.SIGTERM, _graceful_exit)
    except (ValueError, OSError):
        pass  # non-main-thread entry (tests importing main())

    # Fatal-error black box: an uncaught exception on any thread dumps
    # the ring before the default traceback handling runs.
    _prev_hook = sys.excepthook

    def _fatal_hook(tp, val, tb):
        events.record("proc", "fatal_error", error=repr(val))
        events.dump_crash("fatal_error")
        _prev_hook(tp, val, tb)

    sys.excepthook = _fatal_hook

    cw.run_task_loop()
    os._exit(0)


if __name__ == "__main__":
    main()
