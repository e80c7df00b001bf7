"""One load-generating process of a closed loop over chunk needles of
megabytes: `http_client_proc.py`'s loop (a thread to each connection, blocking
sockets, keep-alive, a 503 asked again after Retry-After, latency from the
first send, every body compared as it arrives) with a receive that costs what
the bytes cost and no more. `http_client_proc.Connection.get` grows the body
with `buf += chunk`, which copies what it has at every `recv`: 18 ms of CPU
for one 4 MiB body, so four processes of it are full before the server is.
Here a body is received with `recv_into` into a buffer allocated once from
Content-Length (and kept while the next body has the same length, as three in
four chunks do) and compared with the pool's bytes where they lie
(`bytearray == memoryview` is one memcmp): 0.6 ms a body.

The job is `http_client_proc.py`'s, and:

- `first`: for each connection, the needles it asks for before its
  `warm_gets` drawn ones (the traffic shares out the needles that lie on a
  lost shard, so that every span the window can rebuild has been rebuilt,
  and its decode's shape compiled, before it);
- `extras_file`: where the counts go that `http_client_proc.py` does not
  print: `bytes_good`, and for the GETs of the window answered with the right
  body what the plain reference says they are made of (`intervals`,
  `lost_intervals`, `lost_needles`: benchmarks/reference/ec_locate.py).

The line printed at the end has `http_client_proc.py`'s keys, so
`http_closed_loop`'s window reads it as it reads that one.
"""

from __future__ import annotations

import array
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks.lib.http_client_proc import Connection, Worker  # noqa: E402

HEAD_BYTES = 65536


class ChunkConnection(Connection):
    def __init__(self, hostport: str):
        super().__init__(hostport)
        self.body = bytearray()

    def get(self, target: str) -> tuple:
        """(status, headers as lower-case bytes, body): the body is this
        connection's buffer, whole until the next `get`."""
        if self.sock is None:
            self.connect()
        self.sock.sendall(b"GET /" + target.encode() + b" HTTP/1.1\r\nHost: bench\r\n\r\n")
        buf = b""
        while True:
            end = buf.find(b"\r\n\r\n")
            if end >= 0:
                break
            chunk = self.sock.recv(HEAD_BYTES)
            if not chunk:
                raise ConnectionError("closed before the headers")
            buf += chunk  # the headers and at most HEAD_BYTES of the body
        head = buf[:end].lower()
        status = int(head[9:12])
        at = head.find(b"content-length:")
        if at < 0:
            raise ConnectionError("no content-length")
        stop = head.find(b"\r", at)
        length = int(head[at + 15 : stop if stop >= 0 else len(head)])
        if len(self.body) != length:
            self.body = bytearray(length)
        have = len(buf) - (end + 4)
        if have > length:
            raise ConnectionError("more bytes than the one answer")
        view = memoryview(self.body)
        view[:have] = buf[end + 4 :]
        while have < length:
            got = self.sock.recv_into(view[have:])
            if not got:
                raise ConnectionError("closed before the body")
            have += got
        return status, head, self.body


class Drawn:
    """The store's reader for one connection: its first draws are given, the
    rest uniform from the seed; the needle drawn last is remembered."""

    def __init__(self, reader, first: list):
        self.reader, self.first, self.last = reader, list(reversed(first)), None

    def draw(self, rng) -> tuple:
        self.last = self.first.pop() if self.first else self.reader.draw_index(rng)
        return self.reader.target(self.last), self.reader.body(self.last)


class ChunkWorker(Worker):
    def __init__(self, job: dict, index: int, reader, first: list, tallies):
        super().__init__({**job, "warm_gets": job["warm_gets"] + len(first)}, index,
                         Drawn(reader, first))
        self.conn = ChunkConnection(job["hostport"])
        self.tallies = tallies
        self.start_counts()

    def start_counts(self) -> None:
        self.bytes_good = 0
        self.made_of = [0, 0, 0]

    def one(self) -> float:
        good = self.good
        seconds = super().one()
        if self.good > good:
            self.bytes_good += len(self.conn.body)
            for j, count in enumerate(self.tallies[self.reader.last]):
                self.made_of[j] += int(count)
        return seconds


def main() -> None:
    job = json.loads(sys.stdin.readline())
    store = job["store"]
    reader = importlib.import_module(f"benchmarks.lib.stores.{store['kind']}").Reader(
        store, job["seed"], job["pick"]
    )
    tallies = reader.tallies()
    workers = [ChunkWorker(job, job["first_index"] + j, reader, job["first"][j], tallies)
               for j in range(job["connections"])]
    for w in workers:
        w.start()
    for w in workers:
        w.ready.wait()
    for w in workers:  # each waits for `go`: the warm-up's counts end here
        w.start_counts()
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
    extras = {"bytes_good": sum(w.bytes_good for w in workers),
              **{key: sum(w.made_of[j] for w in workers)
                 for j, key in enumerate(("intervals", "lost_intervals", "lost_needles"))}}
    with open(job["extras_file"], "w") as f:
        json.dump(extras, f)
    print(json.dumps({
        **extras,
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
