"""Worker process — a TaskManager-analog running one job attempt.

The reference runs long-lived TaskManager actors that register with the
JobManager, host task slots and heartbeat over Akka
(TaskManager.scala:296 registration+heartbeats; DeathWatch at :311).
TPU-adapted prototype: a worker is one OS process owning the accelerator
for one job attempt (the per-job container pattern the reference's
YARN/Mesos modes use). It:

  1. registers with the controller over the JSON/TCP control protocol,
  2. heartbeats on an interval (controller marks it dead on timeout OR
     on process exit — the DeathWatch analog),
  3. builds the job from an importable builder reference
     ("pkg.mod:fn" or "path/to/file.py:fn" — the user-code shipping
     seam, ref BlobServer/jar distribution),
  4. executes with checkpointing enabled, restoring from the latest
     checkpoint when respawned after a failure,
  5. reports terminal status back to the controller.

Run: python -m flink_tpu.runtime.worker --controller PORT --worker-id W
     --builder REF --job-name NAME --checkpoint-dir DIR [--restore]
"""

from __future__ import annotations

import argparse
import importlib
import importlib.util
import os
import sys
import threading
import traceback


def load_builder(ref: str):
    """Resolve "module:function" or "/path/file.py:function"."""
    modpart, _, fnname = ref.rpartition(":")
    if not modpart:
        raise ValueError(f"builder ref {ref!r} must be 'module:function'")
    if modpart.endswith(".py") or os.path.sep in modpart:
        spec = importlib.util.spec_from_file_location("_job_builder", modpart)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    else:
        mod = importlib.import_module(modpart)
    return getattr(mod, fnname)


def parse_controller(addr: str) -> tuple:
    """'HOST:PORT' (multi-host registration, TaskManager.scala:296) or a
    bare port (single-host back-compat) -> (host, port)."""
    if ":" in addr:
        host, _, port = addr.rpartition(":")
        return host, int(port)
    return "127.0.0.1", int(addr)


def _send(controller: tuple, msg: dict, timeout_s: float = 5.0) -> dict:
    from flink_tpu.runtime.cluster import control_request

    host, port = controller
    return control_request(host, port, msg, timeout_s=timeout_s)


def run_worker(controller, worker_id: str, builder_ref: str,
               job_name: str, checkpoint_dir: str, restore: bool,
               heartbeat_s: float = 0.5) -> int:
    if isinstance(controller, int):
        controller = ("127.0.0.1", controller)
    _send(controller, {
        "action": "register-worker", "worker_id": worker_id,
        "pid": os.getpid(),
        # lets a controller that did not spawn this worker ADOPT it with
        # full context (external TaskManager registration)
        "builder": builder_ref, "job_name": job_name,
        "checkpoint_dir": checkpoint_dir,
    })

    stop = threading.Event()

    def beat():
        while not stop.is_set():
            try:
                _send(controller, {
                    "action": "heartbeat", "worker_id": worker_id,
                })
            except OSError:
                pass          # controller briefly unreachable; keep trying
            stop.wait(heartbeat_s)

    hb = threading.Thread(target=beat, daemon=True, name="worker-heartbeat")
    hb.start()

    status, error = "FINISHED", None
    try:
        builder = load_builder(builder_ref)
        env = builder()
        if checkpoint_dir:
            interval = env.checkpoint_interval_steps or 4
            env.enable_checkpointing(interval, checkpoint_dir)
        restore_from = None
        if restore and checkpoint_dir:
            from flink_tpu.runtime.checkpoint import CheckpointStorage

            st = CheckpointStorage(checkpoint_dir)
            if st.latest() is not None:
                restore_from = checkpoint_dir
        env.execute(job_name, restore_from=restore_from)
    except Exception as e:
        status, error = "FAILED", "".join(
            traceback.format_exception_only(type(e), e)
        ).strip()
    finally:
        stop.set()
        try:
            _send(controller, {
                "action": "worker-status", "worker_id": worker_id,
                "status": status, "error": error,
            })
        except OSError:
            pass
    return 0 if status == "FINISHED" else 1


def main(argv=None) -> int:
    # a worker that cannot get its chip fails; test workers name
    # JAX_PLATFORMS=cpu and run on the virtual CPU mesh
    from flink_tpu.core.jaxenv import refuse_cpu_fallback

    refuse_cpu_fallback()

    ap = argparse.ArgumentParser()
    ap.add_argument("--controller", required=True,
                    help="HOST:PORT of the controller (or bare port)")
    ap.add_argument("--worker-id", required=True)
    ap.add_argument("--builder", required=True)
    ap.add_argument("--job-name", default="job")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--restore", action="store_true")
    ap.add_argument("--heartbeat-s", type=float, default=0.5)
    a = ap.parse_args(argv)
    return run_worker(parse_controller(a.controller), a.worker_id,
                      a.builder, a.job_name, a.checkpoint_dir, a.restore,
                      a.heartbeat_s)


if __name__ == "__main__":
    sys.exit(main())
