"""One load-generating process of a closed loop: a thread to each connection,
blocking sockets, HTTP/1.1 keep-alive. The benchmark's own client, so that no
change to the program's client library moves the load.

The job comes as one JSON line on standard input. The process connects, sends
`warm_gets` requests on every connection, prints `ready`, waits for `go`, and
then each connection GETs `/<fid>` for a file drawn uniformly from the seed
(the store's `Reader` says which files there are and what each has to return),
the next request when the reply is in, until the deadline; a request in flight
at that moment is finished and its latency kept, but only the replies that were
in by the deadline count towards the rate. A 503 is asked again after Retry-After,
for up to a minute past the deadline, and the latency runs from the first send.
Every body is compared with what the store builder wrote as it arrives. Raw
latencies go to a file of float64 seconds, the counts to one JSON line on
standard output.
"""

from __future__ import annotations

import array
import importlib
import json
import os
import random
import socket
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

MAX_ASKS = 400
LATE_S = 60.0  # how long past the window's close a GET is still asked again
FAILED_S = 1e6  # a GET that never got its body: slower than any limit


class Connection:
    def __init__(self, hostport: str):
        host, port = hostport.split(":")
        self.addr = (host, int(port))
        self.sock = None
        self.buf = b""

    def connect(self) -> None:
        self.sock = socket.create_connection(self.addr, timeout=60)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.buf = b""

    def get(self, target: str) -> tuple:
        """(status, headers as lower-case bytes, body)."""
        if self.sock is None:
            self.connect()
        self.sock.sendall(b"GET /" + target.encode() + b" HTTP/1.1\r\nHost: bench\r\n\r\n")
        buf = self.buf
        while True:
            end = buf.find(b"\r\n\r\n")
            if end >= 0:
                break
            chunk = self.sock.recv(65536)
            if not chunk:
                raise ConnectionError("closed before the headers")
            buf += chunk
        head = buf[:end].lower()
        status = int(head[9:12])
        at = head.find(b"content-length:")
        if at < 0:
            raise ConnectionError("no content-length")
        stop = head.find(b"\r", at)
        length = int(head[at + 15 : stop if stop >= 0 else len(head)])
        need = end + 4 + length
        while len(buf) < need:
            chunk = self.sock.recv(65536)
            if not chunk:
                raise ConnectionError("closed before the body")
            buf += chunk
        self.buf = buf[need:]
        return status, head, buf[end + 4 : need]

    def close(self) -> None:
        if self.sock is not None:
            self.sock.close()
            self.sock = None


def retry_after(head: bytes) -> float:
    at = head.find(b"retry-after:")
    if at < 0:
        return 0.02
    stop = head.find(b"\r", at)
    try:
        return max(0.02, float(head[at + 12 : stop if stop >= 0 else len(head)]))
    except ValueError:
        return 0.02


class Worker(threading.Thread):
    def __init__(self, job: dict, index: int, reader):
        super().__init__(daemon=True)
        self.job, self.reader = job, reader
        self.rng = random.Random(job["seed"] * 4096 + index)
        self.conn = Connection(job["hostport"])
        self.latency = array.array("d")
        self.good = self.wrong = self.unanswered = self.shed = self.sent = 0
        self.good_by_deadline = 0
        self.start_at = self.end_at = 0.0
        self.ready = threading.Event()
        self.go = threading.Event()
        self.deadline = 0.0
        self.error = None

    def one(self) -> float:
        """One GET, asked again while shed; its latency, or FAILED_S."""
        target, want = self.reader.draw(self.rng)
        t0 = time.perf_counter()
        for _ in range(MAX_ASKS):
            if self.deadline and time.perf_counter() > self.deadline + LATE_S:
                break
            self.sent += 1
            try:
                status, head, body = self.conn.get(target)
            except (OSError, ValueError) as e:
                self.error = f"{type(e).__name__}: {e}"
                self.conn.close()
                time.sleep(0.02)
                continue
            if status == 503:
                self.shed += 1
                time.sleep(retry_after(head))
                continue
            if status == 200 and body == want:
                self.good += 1
                return time.perf_counter() - t0
            self.wrong += 1
            self.error = f"GET {target}: {status}, {len(body)} bytes, {body[:16]!r}"
            return FAILED_S
        self.unanswered += 1
        return FAILED_S

    def run(self) -> None:
        for _ in range(self.job["warm_gets"]):
            self.one()
        self.warm = (self.good, self.wrong, self.unanswered)
        self.good = self.wrong = self.unanswered = self.shed = self.sent = 0
        self.ready.set()
        self.go.wait()
        self.start_at = time.perf_counter()
        while time.perf_counter() < self.deadline:
            self.latency.append(self.one())
            if time.perf_counter() <= self.deadline:
                self.good_by_deadline = self.good
        self.end_at = time.perf_counter()
        self.conn.close()


def main() -> None:
    job = json.loads(sys.stdin.readline())
    store = job["store"]
    reader = importlib.import_module(f"benchmarks.lib.stores.{store['kind']}").Reader(
        store, job["seed"], job["pick"]
    )
    workers = [Worker(job, job["first_index"] + j, reader) for j in range(job["connections"])]
    for w in workers:
        w.start()
    for w in workers:
        w.ready.wait()
    warm_bad = sum(w.warm[1] + w.warm[2] for w in workers)
    print(json.dumps({"ready": True, "warm_bad": warm_bad,
                      "warm_good": sum(w.warm[0] for w in workers),
                      "error": next((w.error for w in workers if w.error), None)}), flush=True)
    line = sys.stdin.readline().split()
    if not line or line[0] != "go":
        return
    cpu0 = time.process_time()
    deadline = float(line[1])  # on time.perf_counter()'s clock, which processes share
    for w in workers:
        w.deadline = deadline
        w.go.set()
    for w in workers:
        w.join()
    cpu1 = time.process_time()
    all_latency = array.array("d")
    for w in workers:
        all_latency.extend(w.latency)
    with open(job["latency_file"], "wb") as f:
        all_latency.tofile(f)
    print(json.dumps({
        "good": sum(w.good for w in workers),
        "good_by_deadline": sum(w.good_by_deadline for w in workers), "wrong": sum(w.wrong for w in workers),
        "unanswered": sum(w.unanswered for w in workers),
        "shed": sum(w.shed for w in workers), "sent": sum(w.sent for w in workers),
        "start_at": min(w.start_at for w in workers), "end_at": max(w.end_at for w in workers),
        "cpu_s": cpu1 - cpu0,
        "error": next((w.error for w in workers if w.error), None),
    }), flush=True)


if __name__ == "__main__":
    main()
