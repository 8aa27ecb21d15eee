"""The load generator: a process of its own that never imports jax.

Started by the harness (``python -m benchmark.loadgen``) before the
daemon is built, so that payloads are encoded while the corpus is
ingested; killed with it. Protocol on the pipes, one JSON object a line:

    stdin   job    {"config", "kind", "params", "seed", "seconds"}
    stdout  ready  {"event": "ready", "requests": N, "encode_s": S}
    stdin   go     {"port": P}
    stdout  result {"event": "result", "requests": [...], "answers": {...}}

Open loop: one dispatcher hands each request to a bounded pool of sender
threads at its due instant; latency counts from the DUE instant, so the
wait a stall imposes on later requests is in it, and ``lag_ms`` (sent -
due) says how late the generator itself ran. Closed loop: ``clients``
threads, each sending its next request when the last returned, starting
none after the window has closed. A refused, failed or timed-out request
is ``ok: false`` and misses every latency.
"""

from __future__ import annotations

import json
import queue
import socket
import sys
import threading
import time
from typing import Any, Dict, List, Optional

from benchmark import data, spec


def encode_payloads(config: Dict[str, Any], plan: Dict[str, Any],
                    seed: int, k: int) -> List[bytes]:
    out = []
    for i, (nq, dbg) in enumerate(zip(plan["sizes"], plan["debug"])):
        obj = {"op": "query", "id": str(i), "k": int(k),
               "queries": data.request_queries(config, seed, i,
                                               nq).tolist()}
        if dbg:
            obj["debug"] = True
        out.append((json.dumps(obj, separators=(",", ":")) + "\n").encode())
    return out


class _Sender:
    """One persistent connection; one request outstanding at a time."""

    def __init__(self, port: int, timeout_s: float):
        self.port, self.timeout_s = port, timeout_s
        self.sock: Optional[socket.socket] = None
        self.rfile = None

    def open(self) -> None:
        self.sock = socket.create_connection(("127.0.0.1", self.port),
                                             timeout=self.timeout_s)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.rfile = self.sock.makefile("rb")

    def close(self) -> None:
        for f in (self.rfile, self.sock):
            try:
                if f is not None:
                    f.close()
            except OSError:
                pass
        self.sock = self.rfile = None

    def call(self, payload: bytes) -> Dict[str, Any]:
        try:
            if self.sock is None:
                self.open()
            self.sock.sendall(payload)
            line = self.rfile.readline()
            if not line:
                raise ConnectionError("daemon closed the connection")
            return json.loads(line)
        except (OSError, ValueError) as e:
            self.close()          # a half-read reply would poison the next
            return {"ok": False, "error": f"{type(e).__name__}: {e}"}


def _record(rec: Dict[str, Any], resp: Dict[str, Any],
            answers: Dict[str, Any]) -> None:
    rec["ok"] = bool(resp.get("ok"))
    if not rec["ok"]:
        rec["error"] = str(resp.get("error", "no reply"))[:200]
        return
    ans = {"labels": resp["labels"], "checksums": resp["checksums"]}
    if "dists" in resp:
        ans["dists"], ans["neighbors"] = resp["dists"], resp["neighbors"]
    answers[str(rec["seq"])] = ans


def run_open(plan, payloads, port, timeout_s):
    n = len(payloads)
    recs: List[Dict[str, Any]] = [None] * n
    answers: Dict[str, Any] = {}
    jobs: queue.Queue = queue.Queue()
    connected = threading.Semaphore(0)
    clock = {}                      # "t0" once every sender is connected

    def worker() -> None:
        snd = _Sender(port, timeout_s)
        try:
            snd.open()
        except OSError:
            pass                    # call() tries again and reports it
        connected.release()
        while True:
            i = jobs.get()
            if i is None:
                break
            due = clock["t0"] + plan["due_s"][i]
            sent = time.monotonic()
            resp = snd.call(payloads[i])
            done = time.monotonic()
            rec = {"seq": i, "payload": i, "nq": plan["sizes"][i],
                   "debug": plan["debug"][i], "due_s": plan["due_s"][i],
                   "lag_ms": (sent - due) * 1e3,
                   "latency_ms": (done - due) * 1e3,
                   "done_s": done - clock["t0"]}
            _record(rec, resp, answers)
            recs[i] = rec
        snd.close()

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(plan["clients"])]
    for t in threads:
        t.start()
    for _ in threads:
        connected.acquire()
    t0 = clock["t0"] = time.monotonic() + 0.05
    for i in range(n):
        delay = t0 + plan["due_s"][i] - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        jobs.put(i)
    for _ in threads:
        jobs.put(None)
    for t in threads:
        t.join()
    return recs, answers, plan["due_s"][-1]


def run_closed(plan, payloads, port, seconds, timeout_s):
    recs: List[Dict[str, Any]] = []
    answers: Dict[str, Any] = {}
    lock = threading.Lock()
    counter = [0]
    t0 = time.monotonic() + 0.1

    def client() -> None:
        snd = _Sender(port, timeout_s)
        delay = t0 - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        while time.monotonic() - t0 < seconds:
            with lock:
                seq = counter[0]
                counter[0] += 1
            p = seq % len(payloads)
            sent = time.monotonic()
            resp = snd.call(payloads[p])
            done = time.monotonic()
            rec = {"seq": seq, "payload": p, "nq": plan["sizes"][p],
                   "debug": plan["debug"][p], "due_s": sent - t0,
                   "lag_ms": 0.0, "latency_ms": (done - sent) * 1e3,
                   "done_s": done - t0}
            with lock:
                _record(rec, resp, answers)
                recs.append(rec)
        snd.close()

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(plan["clients"])]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    recs.sort(key=lambda r: r["seq"])
    return recs, answers, seconds


def main() -> int:
    job = json.loads(sys.stdin.readline())
    kind = spec.traffic_kind(job["kind"])
    t = time.monotonic()
    plan = kind.plan(job["params"], job["seed"], job["seconds"])
    payloads = encode_payloads(job["config"], plan, job["seed"],
                               job["params"]["k"])
    print(json.dumps({"event": "ready", "requests": len(payloads),
                      "encode_s": time.monotonic() - t}), flush=True)
    go = json.loads(sys.stdin.readline())
    timeout_s = float(job["params"].get("timeout_s", 30.0))
    if plan["mode"] == "open":
        recs, answers, span = run_open(plan, payloads, go["port"],
                                       timeout_s)
    else:
        recs, answers, span = run_closed(plan, payloads, go["port"],
                                         float(job["seconds"]), timeout_s)
    print(json.dumps({"event": "result", "requests": recs,
                      "answers": answers, "offered_s": span,
                      "timeout_ms": timeout_s * 1e3}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
