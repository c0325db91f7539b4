"""
The child of a serve cell: holds the chip, builds the served collection
with ``build-fleet`` (set-up), runs ``run-server`` with default knobs in
its main thread, and answers the parent's control messages from another
thread: where the window starts and ends, when to trace, what the
process counted. The parent ends it with SIGTERM, through the server's
own drain.
"""

import faulthandler
import json
import os
import signal
import sys
import threading
from multiprocessing.connection import Listener

from common import (
    NoChip, Trace, build_job, compiled_between, die_with_parent, memory, run_cli, start,
)
from jobs import read_status

def main(spec_path: str) -> int:
    die_with_parent()
    with open(spec_path) as f:
        spec = json.load(f)
    try:
        device, counter, errors = start(
            spec["chips"], os.path.join(spec["run_dir"], "child.log")
        )
    except NoChip as exc:
        print(f"chipbench: {exc}", file=sys.stderr)
        return 3
    return serve(spec, device, counter, errors)


def build_collection(spec: dict) -> dict:
    """The served collection: one ``build-fleet`` job of the cell's
    configuration into ``spec["collection_dir"]`` (whose basename is the
    collection's revision)."""
    from harness.data import machines_document

    traffic = spec["traffic"]
    document = machines_document(
        spec["config"], spec["seed"], "srv",
        traffic["machines_served"], traffic["collection_history_days"],
    )
    output_dir = spec["collection_dir"]
    record = build_job(document, os.path.dirname(output_dir), output_dir)
    record["index"] = "srv"
    record["status"] = read_status(record["output_dir"])
    return record


class Control:
    """The parent's side channel: one connection, one JSON message in,
    one out. Runs beside the server, in a thread of its own."""

    def __init__(self, spec: dict, device: dict, counter, errors, collection: dict):
        self.spec, self.device, self.counter, self.errors = spec, device, counter, errors
        self.collection = collection
        self.trace = None
        self.marks = {}
        self.listener = Listener(
            ("127.0.0.1", spec["control_port"]), authkey=spec["authkey"].encode()
        )

    def serve_forever(self) -> None:
        with self.listener, self.listener.accept() as connection:
            while True:
                try:
                    message = json.loads(connection.recv_bytes())
                except (EOFError, OSError):
                    return
                connection.send_bytes(json.dumps(self.handle(message), default=str).encode())

    def handle(self, message: dict) -> dict:
        command = message["cmd"]
        if command == "window_start":
            from gordo_tpu import ingest

            ingest.reset_ingest_stats()
            self.marks["before"] = self.counter.snapshot()
            self.marks["errors"] = len(self.errors.records)
        elif command == "trace_start":
            self.trace = Trace(os.path.join(self.spec["run_dir"], "trace"))
            self.trace.start()
        elif command == "trace_stop":
            self.trace.stop()
        elif command == "window_end":
            return self.report()
        return {"ok": True}

    def report(self) -> dict:
        from gordo_tpu import ingest
        from gordo_tpu.server import fleet_store

        after = self.counter.snapshot()
        before = self.marks["before"]
        return {
            "device": {**self.device, **memory(self.spec["chips"])},
            "compiles": {"before_window": before, "after_window": after},
            "in_window": compiled_between(before, after, self.device.get("compile_cache")),
            "ingest": ingest.ingest_stats(),
            "program_cache": fleet_store.program_cache_stats(),
            "serving_backend": fleet_store.serving_backend(),
            "errors_logged": self.errors.records[self.marks["errors"]:],
            "collection": self.collection,
            "trace": self.trace.reduce(self.spec["chips"]) if self.trace else None,
        }


def serve(spec: dict, device: dict, counter, errors) -> int:
    """Build, then serve until SIGTERM. A function of ``spec``, so the
    tests run it tiny on the CPU."""
    # SIGUSR1 from the parent: every thread's stack into child.out
    faulthandler.register(signal.SIGUSR1, all_threads=True)
    collection = build_collection(spec)
    print(f"collection built in {collection['seconds']:.1f}s", flush=True)
    control = Control(spec, device, counter, errors, collection)
    thread = threading.Thread(target=control.serve_forever, name="chipbench-control", daemon=True)
    thread.start()
    # until the server installs its own handler, SIGTERM must not end
    # this process before the drain exists
    signal.signal(signal.SIGTERM, lambda signum, frame: sys.exit(0))
    os.environ["MODEL_COLLECTION_DIR"] = collection["output_dir"]
    return run_cli(
        ["run-server", "--host", "127.0.0.1", "--port", str(spec["port"])]
        + list(spec["traffic"].get("server_args", []))
    )


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
