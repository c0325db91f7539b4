"""
Traffic of a serve cell: closed-loop clients over HTTP against a
``run-server`` that the child runs over a collection it built.

Parameters (the cell's traffic file): ``clients`` (each sends its next
request when the last is answered), ``route`` (bodies are Arrow-IPC X
and y, answers Arrow), ``machines_served`` and
``collection_history_days`` (the collection ``build-fleet`` makes in
set-up), ``machine_zipf`` (machine popularity, Zipf exponent),
``rows_choices`` (rows a request, drawn uniformly from this list: clients
ask for whole days, and the server's ingest compiles once per distinct
row count, so a continuous draw would compile on every request), ``pool``
(distinct request bodies, encoded before the window), ``verify_responses``
(responses kept as bytes and compared with the reference afterwards),
``trace_seconds`` and ``trace_after_seconds`` (the traced slice),
``warmup_clients`` (1: every shape compiles alone, not ten at once) and
``warmup_timeout_seconds`` (after which a server that does not answer is
given up),
``server_args`` (appended to ``run-server``). Every draw comes from
``--seed``. Warm-up sends every body of the pool once, so every shape
the window uses has compiled before it: that is set-up.
"""

import http.client
import json
import os
import resource
import secrets
import shutil
import signal
import socket
import threading
import time
from multiprocessing.connection import Client
from typing import Any, Dict, List, Optional

from harness import correct
from harness.child import end_child, start_child, tail
from harness.data import PROJECT, machine_names, request_rows
from harness.stats import percentile

PROC = "serve_worker"
ARROW = "application/vnd.apache.arrow.stream"
#: the served collection directory's basename is its revision
REVISION = "1700000000000"


def free_port() -> int:
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


def draw_requests(traffic: Dict[str, Any], seed: int) -> List[Dict[str, int]]:
    """The pool's draws: which machine (Zipf over the served machines,
    rank = machine index) and how many rows (one of ``rows_choices``)."""
    import numpy as np

    rng = np.random.RandomState(seed)
    ranks = np.arange(1, traffic["machines_served"] + 1, dtype=np.float64)
    popularity = ranks ** -float(traffic["machine_zipf"])
    machines = rng.choice(
        traffic["machines_served"], traffic["pool"], p=popularity / popularity.sum()
    )
    rows = rng.choice(traffic["rows_choices"], traffic["pool"])
    return [{"machine": int(m), "rows": int(r)} for m, r in zip(machines, rows)]


def parse_server_timing(header: Optional[str]) -> Dict[str, float]:
    """``name;dur=ms, ...`` -> name -> milliseconds."""
    stages: Dict[str, float] = {}
    for entry in (header or "").split(","):
        name, _, duration = entry.strip().partition(";dur=")
        if name and duration:
            try:
                stages[name] = float(duration)
            except ValueError:
                pass
    return stages


class Clients:
    """``n`` closed-loop clients over one pool of bodies. A request is
    timed from send to last byte; bodies are not decoded in the window."""

    def __init__(self, port: int, pool: List[Dict[str, Any]], n: int, keep: frozenset):
        self.port, self.pool, self.n, self.keep = port, pool, n, keep
        self.records: List[Dict[str, Any]] = []
        self.kept: Dict[int, bytes] = {}
        self._lock = threading.Lock()

    def _client(self, order: List[int], start_at: int, deadline: Optional[float]) -> None:
        connection = http.client.HTTPConnection("127.0.0.1", self.port, timeout=120)
        try:
            for sequence in range(start_at, len(order), self.n):
                if deadline is not None and time.monotonic() >= deadline:
                    break
                entry = self.pool[order[sequence]]
                sent_wall, sent = time.time(), time.monotonic()
                try:
                    connection.request("POST", entry["path"], entry["body"], entry["headers"])
                    response = connection.getresponse()
                    payload = response.read()
                    status, timing = response.status, response.getheader("Server-Timing")
                except (OSError, http.client.HTTPException) as exc:
                    connection.close()
                    status, timing, payload = 599, None, repr(exc).encode()
                record = {
                    "sequence": sequence, "pool": order[sequence], "sent": sent_wall,
                    "seconds": time.monotonic() - sent, "status": status,
                    "rows": entry["rows"], "timing": timing, "bytes": len(payload),
                }
                with self._lock:
                    self.records.append(record)
                    if sequence in self.keep or status != 200:
                        self.kept[sequence] = payload
        finally:
            connection.close()

    def run(self, order: List[int], seconds: Optional[float]) -> None:
        """Send ``order`` (indices into the pool) from ``n`` threads,
        thread ``t`` taking positions ``t, t+n, ...``; stop issuing after
        ``seconds`` (None: send all of ``order``)."""
        deadline = None if seconds is None else time.monotonic() + seconds
        threads = [
            threading.Thread(target=self._client, args=(order, t, deadline))
            for t in range(self.n)
        ]
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join()


def encode_arrow(X, y) -> bytes:
    """``X`` and ``y`` as one Arrow IPC stream, as gordo's client sends
    them: the index first, then one column a tag, each field tagged with
    its role. Copied from ``gordo_tpu/server/wire/arrow_codec.py:
    encode_request`` (a test holds the two byte for byte), so that the
    load generator needs nothing of the program: importing ``gordo_tpu``
    imports JAX, and the process that offers the load stays off JAX
    while the child holds the chip."""
    import numpy as np
    import pyarrow as pa

    arrays = [pa.array(X.index)]
    fields = [pa.field("__index__", arrays[0].type, metadata={b"gordo:role": b"index"})]
    for frame, role in ((X, b"x"), (y, b"y")):
        for name in frame.columns:
            array = pa.array(np.asarray(frame[name]))
            fields.append(pa.field(str(name), array.type, metadata={b"gordo:role": role}))
            arrays.append(array)
    schema = pa.schema(fields)
    sink = pa.BufferOutputStream()
    with pa.ipc.new_stream(sink, schema) as writer:
        writer.write_batch(pa.record_batch(arrays, schema=schema))
    return sink.getvalue().to_pybytes()


def encode_pool(cell, seed: int, collection_dir: str) -> List[Dict[str, Any]]:
    """The request bodies, drawn and encoded before the window."""
    import numpy as np

    traffic = cell.traffic
    names = machine_names(seed, "srv", traffic["machines_served"])
    rng = np.random.RandomState(seed + 1)
    ranges: Dict[str, Any] = {}
    pool = []
    for draw in draw_requests(traffic, seed):
        name = names[draw["machine"]]
        if name not in ranges:
            with open(os.path.join(collection_dir, name, "metadata.json")) as f:
                meta = json.load(f)["metadata"]["build_metadata"]
            ranges[name] = meta["dataset"]["dataset_meta"]["x_hist"]
        X = request_rows(ranges[name], draw["rows"], rng)
        pool.append(
            {
                "machine": name,
                "rows": draw["rows"],
                "X": X,
                "path": f"/gordo/v0/{PROJECT}/{name}/{traffic['route']}",
                "body": encode_arrow(X, X),
                "headers": {"Content-Type": ARROW, "Accept": ARROW},
            }
        )
    return pool


def wait_for(url_port: int, child, run_dir: str, timeout: float = 1000.0) -> None:
    deadline = time.monotonic() + timeout
    while True:
        if child.poll() is not None:
            raise RuntimeError(f"{PROC} exited {child.returncode}:\n{tail(run_dir)}")
        try:
            connection = http.client.HTTPConnection("127.0.0.1", url_port, timeout=5)
            connection.request("GET", "/healthcheck")
            if connection.getresponse().status == 200:
                return
        except (OSError, http.client.HTTPException):
            pass
        finally:
            connection.close()
        if time.monotonic() > deadline:
            raise RuntimeError("the server did not come up")
        time.sleep(0.1)


def check_responses(
    checks: correct.Checks, cell, pool, order, clients: Clients, collection_dir: str, platform: str
) -> None:
    """The kept responses, decoded and held to the reference forward of
    the machine's own weights on the request's rows after the artifact's
    scaler; anomaly columns finite; thresholds present."""
    import numpy as np

    from gordo_tpu.server import wire

    reference = cell.reference()
    artifacts: Dict[str, Any] = {}
    for sequence in sorted(clients.keep):
        payload = clients.kept.get(sequence)
        if payload is None:
            continue  # not reached inside the window
        entry = pool[order[sequence]]
        what = f"response {sequence} ({entry['machine']}, {entry['rows']} rows)"
        try:
            frame, _ = wire.decode_response(payload)
        except Exception as exc:  # noqa: BLE001 - an undecodable body is the finding
            checks.check(False, f"{what}: cannot decode ({exc!r})")
            continue
        if entry["machine"] not in artifacts:
            artifacts[entry["machine"]] = correct.load_artifact(collection_dir, entry["machine"])
        model, metadata = artifacts[entry["machine"]]
        estimator = correct.find_estimator(model)
        expected = reference.forward(
            reference.layers_of(estimator),
            reference.model_input(estimator, correct.host_transform(model, entry["X"])),
        )
        checks.compare(what, frame["model-output"].to_numpy(), expected, platform)
        numeric = frame.select_dtypes("number").to_numpy()
        checks.check(bool(np.isfinite(numeric).all()), f"{what}: non-finite column")
        checks.check(
            "total-anomaly-scaled" in frame.columns.get_level_values(0),
            f"{what}: no anomaly columns in {sorted(set(frame.columns.get_level_values(0)))}",
        )
        thresholds = metadata["model"]["model_meta"].get("feature-thresholds") or []
        checks.check(len(thresholds) == cell.config["tags"], f"{what}: thresholds missing")


def run(cell, seed: int, seconds: float, trace: bool, run_dir: str) -> Dict[str, Any]:
    """Run the cell once; returns the run's evidence (see README.md).
    Nothing here imports ``gordo_tpu`` (and so JAX) while the child is
    alive; the responses are decoded and checked after it has ended."""
    import numpy as np

    traffic = cell.traffic
    spec = {
        "cell": cell.name, "chips": cell.chips, "config": cell.config,
        "traffic": traffic, "seed": seed, "seconds": seconds, "trace": trace,
        "run_dir": run_dir, "port": free_port(), "control_port": free_port(),
        "authkey": secrets.token_hex(16),
        "collection_dir": os.path.join(run_dir, "collection", REVISION),
    }
    collection_dir = spec["collection_dir"]
    began = time.monotonic()

    def said(what: str) -> None:
        print(f"[{time.monotonic() - began:6.1f}s] {what}", flush=True)

    child = start_child(cell.root, PROC, spec)
    try:
        wait_for(spec["port"], child, run_dir)
        pool = encode_pool(cell, seed, collection_dir)
        rng = np.random.RandomState(seed + 2)
        # more draws than any window can send; thousands of requests
        order = [int(i) for i in rng.randint(0, len(pool), 1_000_000)]
        keep = frozenset(int(i) for i in rng.choice(2000, traffic["verify_responses"], replace=False))

        # set-up: every body once, so every shape has compiled
        said(f"server up, {len(pool)} bodies encoded")
        warm = Clients(spec["port"], pool, traffic["warmup_clients"], frozenset())
        warming = threading.Thread(
            target=warm.run, args=(list(range(len(pool))), None), daemon=True
        )
        warming.start()
        warming.join(timeout=traffic["warmup_timeout_seconds"])
        if warming.is_alive():
            child.send_signal(signal.SIGUSR1)  # its stacks into child.out
            time.sleep(2.0)
            raise RuntimeError(
                f"warm-up answered {len(warm.records)} of {len(pool)} requests in "
                f"{traffic['warmup_timeout_seconds']}s:\n{tail(run_dir, 120)}"
            )
        said(f"warm-up done, statuses {sorted({r['status'] for r in warm.records})}")
        clients = Clients(spec["port"], pool, traffic["clients"], keep)
        with Client(("127.0.0.1", spec["control_port"]), authkey=spec["authkey"].encode()) as control:

            def tell(command: str) -> Dict[str, Any]:
                control.send_bytes(json.dumps({"cmd": command}).encode())
                if not control.poll(300.0):
                    child.send_signal(signal.SIGUSR1)
                    time.sleep(2.0)
                    raise RuntimeError(f"no answer to {command}:\n{tail(run_dir, 120)}")
                return json.loads(control.recv_bytes())

            tell("window_start")
            tracer = None
            if trace:
                def traced_slice() -> None:
                    time.sleep(traffic["trace_after_seconds"])
                    tell("trace_start")
                    time.sleep(traffic["trace_seconds"])
                    tell("trace_stop")

                tracer = threading.Thread(target=traced_slice)
                tracer.start()
            cpu_before = resource.getrusage(resource.RUSAGE_SELF)
            window_start = time.time()
            clients.run(order, seconds)
            window_end = time.time()
            cpu_after = resource.getrusage(resource.RUSAGE_SELF)
            said(f"window done, {len(clients.records)} requests")
            if tracer is not None:
                tracer.join()
            report = tell("window_end")
    finally:
        end_child(child, signal.SIGTERM)

    cpu_s = (cpu_after.ru_utime + cpu_after.ru_stime) - (cpu_before.ru_utime + cpu_before.ru_stime)
    try:
        return evaluate(
            cell, seed, report, clients, warm.records, pool, order, collection_dir,
            window_start, window_end, cpu_s,
        )
    finally:
        shutil.rmtree(os.path.join(run_dir, "collection"), ignore_errors=True)


def evaluate(
    cell, seed: int, report: Dict[str, Any], clients: Clients, warm_records,
    pool, order, collection_dir: str, window_start: float, window_end: float,
    cpu_s: float,
) -> Dict[str, Any]:
    """From what the clients recorded and the child reported to the
    run's evidence: ``correct``, the counts and the end-to-end metrics.
    Any response but a 200, in the window or in warm-up, any ERROR the
    server logged, an incomplete collection or a response off the
    reference makes the run incorrect."""
    traffic = cell.traffic
    records = sorted(clients.records, key=lambda r: r["sequence"])
    good = [r for r in records if r["status"] == 200]
    checks = correct.Checks()
    for record in records:
        if record["status"] != 200:
            checks.check(
                False,
                f"request {record['sequence']}: HTTP {record['status']} "
                f"{clients.kept.get(record['sequence'], b'')[:120]!r}",
            )
    for line in warm_records:
        checks.check(line["status"] == 200, f"warm-up request: HTTP {line['status']}")
    for line in report["errors_logged"]:
        checks.check(False, f"error logged in the window: {line}")
    collection = report["collection"]
    names = machine_names(seed, "srv", traffic["machines_served"])
    checks.check(
        correct.check_build_job(checks, collection, names, cell.config) == len(names),
        "the served collection is incomplete",
    )
    check_responses(
        checks, cell, pool, order, clients, collection_dir, report["device"]["platform"]
    )
    latencies = [r["seconds"] * 1000.0 for r in good]
    window_s = window_end - window_start
    report = dict(report)
    report.update(
        {
            "window": {"start": window_start, "end": window_end},
            "attempted": len(records),
            "failed": len(records) - len(good),
            "correct": checks.ok,
            "failures": checks.failures,
            "worst_fraction_of_scale": checks.worst_fraction,
            "requests": [
                {**{k: r[k] for k in ("sequence", "sent", "seconds", "status", "rows")},
                 "stages": parse_server_timing(r["timing"])}
                for r in records
            ],
            "end_to_end": {
                "rows_scored_per_s": sum(r["rows"] for r in good) / window_s,
                "request_p50_ms": percentile(latencies, 50) if latencies else float("nan"),
                "request_p99_ms": percentile(latencies, 99) if latencies else float("nan"),
            },
            "notes": [
                f"requests {len(records)} ({len(good)} answered 200) from "
                f"{traffic['clients']} closed-loop clients in {window_s:.3f}s; "
                f"{len(latencies)} latency samples, so "
                f"{len(latencies) // 100} lie beyond the 99th percentile; "
                f"load generator CPU {cpu_s:.2f}s of {window_s:.2f}s; "
                f"warm-up requests {len(warm_records)}; "
                f"collection build {collection['seconds']:.2f}s",
            ],
        }
    )
    return report
