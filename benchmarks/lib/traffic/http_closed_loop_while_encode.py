"""Traffic kind `http_closed_loop_while_encode`: two loads in one window on
one server. The foreground is `http_closed_loop`, all of it (its set-up,
load clients, window and checks); the background is `shell_jobs`'s
conversion, paced: one `background.command` due every `background.period_s`
from the window's opening, in one shell REPL that took the lock once.

Parameters besides `http_closed_loop`'s, under `background`: `command` with
`{vid}` in it, `reply` that a good answer holds, `warm_volume` (converted
in set-up, so every shape of the encode has compiled before the window),
`first_volume` (the first of `volumes` volumes the window converts, in
order), `period_s`, `sample_one_row_in`.

The pace is fixed, not closed: conversion i is due at i * period_s; one due
while the previous one runs starts when that one ends, as a maintenance pass
does; one due in the window that has not started when the window closes is
behind (`conversions_behind`, limit 0), so every run of every tree does the
same background work, and a faster GET bought by a slower pass is a failure,
not a gain. A conversion is timed from its command to its reply line.

After each conversion, outside its own span and beside the next one (on a
thread of this process, in the order they finished): its 14 shard files'
sizes, and a digest of each block of a seeded `1/sample_one_row_in` of its
rows, compared with the benchmark's own codec's digests of the same rows
(the template is the same bytes for every volume, so the reference is
computed once, while the server starts); then the shards of the conversion
before it are unmounted and deleted over gRPC. The window's last
conversion is kept and compared byte for byte once the server is gone, as
`shell_jobs` compares its last one.

What the window adds to `http_closed_loop`'s numbers (client terms):
`convert_s`, the conversions' own spans inside the window, and `late_ms`,
the mean delay of a conversion's start behind its due time.
"""

from __future__ import annotations

import concurrent.futures as cf
import math
import os
import threading
import time

import numpy as np

from ...reference import rs_codec
from .. import common
from ..rpc import Rpc
from ..shell import Repl
from ..stores import sealed_template
from . import http_closed_loop, shell_jobs

COMPILES = "seaweedfs_tpu_jax_compiles_total"
DISPATCHES = "seaweedfs_tpu_rs_dispatches_total"
ENCODED_BYTES = shell_jobs.ENCODED_BYTES
DIGEST = shell_jobs.DIGEST_BYTES


class Traffic(http_closed_loop.Traffic):
    def __init__(self, ctx):
        super().__init__(ctx)
        bg = ctx.params["background"]
        self.k = int(ctx.config["geometry"]["data_shards"])
        self.m = int(ctx.config["geometry"]["parity_shards"])
        self.period = float(bg["period_s"])
        self.warm_volume = int(bg["warm_volume"])
        self.first = int(bg["first_volume"])
        self.volumes = int(bg["volumes"])
        self.one_in = int(bg["sample_one_row_in"])
        self.repl = self.rpc = self.volume_server = None
        # one thread, in order: the reference's digests, then each
        # conversion's check and the drop of the one before it
        self.checks = cf.ThreadPoolExecutor(1, thread_name_prefix="background-checks")
        self.reference = None  # the codec's digests of every row, in row order
        self.conversions: list = []  # {vid, due, start, at, stop, good}
        self.sampled = {"blocks": 0, "differing": 0, "missized": 0}
        self.kept = None
        self.error = None

    # ---- set-up
    def stage(self, server) -> None:
        super().stage(server)
        for vid in (self.warm_volume, *range(self.first, self.first + self.volumes)):
            sealed_template.link_volume(self.ctx.store, server.data_dir, vid)
        self.reference = self.checks.submit(
            shell_jobs.reference_digests,
            self.ctx.store, self.k, self.m, self.ctx.pool_map, self.ctx.workers)

    def warm(self, server) -> None:
        server.wait_volumes(len(self.ctx.params.get("link_volumes", [])) + 1 + self.volumes)
        super().warm(server)
        t0 = time.perf_counter()
        self.reference = self.reference.result()
        self.repl = Repl(server.master, os.path.join(self.ctx.scratch, "background_shell.log"))
        self.rpc, self.volume_server = Rpc(), server.volume
        got = self.repl.ask("lock", "locked", 60)
        if "locked" not in got:
            raise common.Failed(f"shell lock: {got!r}")
        if not self._convert(self.warm_volume)["good"]:
            raise common.Failed(f"the warm-up conversion of volume {self.warm_volume} failed")
        self._check_sample(server, self.warm_volume)
        self._drop(self.warm_volume)
        self.sampled = dict.fromkeys(self.sampled, 0)
        common.say("background_warm", seconds=round(time.perf_counter() - t0, 3),
                   volume=self.warm_volume, reference_rows=len(self.reference) // (DIGEST * (self.k + self.m)))

    def _convert(self, vid: int) -> dict:
        bg = self.ctx.params["background"]
        start, at = time.perf_counter(), time.time()
        got = self.repl.ask(bg["command"].format(vid=vid), f"volume {vid}:", 900)
        job = {"vid": vid, "start": start, "at": at, "stop": time.perf_counter(),
               "good": bg["reply"] in got}
        if not job["good"]:
            common.say("job_failed", volume=vid, reply=got[:300])
        return job

    def _check_sample(self, server, vid: int) -> None:
        """Sizes of the 14 files; digests of a seeded share of the rows,
        against the reference's digests of the same rows."""
        base = os.path.join(server.data_dir, str(vid))
        dat_bytes, total = self.ctx.store["dat_bytes"], self.k + self.m
        want = rs_codec.shard_size(dat_bytes, self.k)
        sizes = [os.path.getsize(f) if os.path.exists(f) else -1
                 for f in shell_jobs.shard_paths(base, total)]
        missized = sum(1 for s in sizes if s != want)
        self.sampled["missized"] += missized
        rows = sample_rows(len(rs_codec.row_spans(dat_bytes, self.k)), self.one_in, self.ctx.seed, vid)
        self.sampled["blocks"] += len(rows) * total
        if missized:
            self.sampled["differing"] += len(rows) * total
            return
        row = DIGEST * total
        got = b"".join(shell_jobs.file_digests((base, dat_bytes, rows, self.k, self.m)))
        ref = b"".join(self.reference[r * row:(r + 1) * row] for r in rows)
        self.sampled["differing"] += shell_jobs.digests_differing(got, ref)

    def _drop(self, vid: int) -> None:
        for method in ("VolumeEcShardsUnmount", "VolumeEcShardsDelete"):
            self.rpc.call(self.volume_server, "volume", method,
                          {"volume_id": vid, "collection": "",
                           "shard_ids": list(range(self.k + self.m))})

    # ---- the window
    def due_count(self, seconds: float) -> int:
        """Conversions due inside a window of `seconds`: at 0, period, ..."""
        due = math.ceil(seconds / self.period - 1e-9)
        if due > self.volumes:
            raise common.Failed(f"{due} conversions are due in {seconds} s; the cell gives {self.volumes} volumes")
        return due

    def _background(self, server, t0: float, seconds: float) -> None:
        """The paced conversions, on a thread of their own; each one's
        check goes to `checks` as it ends."""
        try:
            end, due_n = t0 + seconds, self.due_count(seconds)
            for i in range(due_n):
                due = t0 + i * self.period
                wait = due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                if time.perf_counter() >= end:
                    break  # due in the window, not started in it: behind
                job = self._convert(self.first + i)
                job["due"] = due
                self.conversions.append(job)
                server.alive()
                if job["good"]:
                    self.checks.submit(self._after, server, job["vid"])
        except Exception as e:  # noqa: BLE001 — raised again by run()
            self.error = e

    def _after(self, server, vid: int) -> None:
        """A good conversion's check, and the drop of the one kept before
        it: the last to finish is the one kept, whenever the window
        closes."""
        try:
            self._check_sample(server, vid)
            if self.kept is not None:
                self._drop(self.kept)
            self.kept = vid
        except Exception as e:  # noqa: BLE001 — raised again by run()
            self.error = self.error or e

    def run(self, server, seconds: float, tracer) -> dict:
        t0 = time.perf_counter()
        thread = threading.Thread(target=self._background, args=(server, t0, seconds),
                                  name="background-conversions", daemon=True)
        thread.start()
        result = super().run(server, seconds, tracer)
        thread.join(900)
        self.checks.shutdown(wait=True)
        if thread.is_alive() or self.error is not None:
            raise common.Failed(f"the background conversions did not finish: {self.error!r}")
        end = t0 + seconds
        jobs = self.conversions
        walls = [j["stop"] - j["start"] for j in jobs]
        result.update({
            "conversions_due": self.due_count(seconds),
            "conversions_started": len(jobs),
            "conversions_failed": sum(1 for j in jobs if not j["good"]),
            "conversions_behind": self.due_count(seconds) - len(jobs),
            "converted_bytes": sum(1 for j in jobs if j["good"]) * self.ctx.store["dat_bytes"],
            "convert_s": sum(max(0.0, min(j["stop"], end) - max(j["start"], t0)) for j in jobs),
            "late_ms": (1000.0 * sum(j["start"] - j["due"] for j in jobs) / len(jobs)) if jobs else None,
            "conversion_walls_s": [round(w, 4) for w in walls],
            "conversion_wall_median_s": round(float(np.median(walls)), 4) if walls else None,
            "conversions_began_at": [round(j["at"], 3) for j in jobs],  # the wall clock, as spans
            "sampled_blocks": self.sampled["blocks"],
        })
        return result

    def after_window(self, server) -> None:
        self.checks.shutdown(wait=True)
        if self.repl is not None:
            try:
                self.repl.ask("unlock", "unlocked", 30)
            finally:
                self.repl.close()
                self.repl = None
        if self.rpc is not None:
            self.rpc.close()
            self.rpc = None
        super().after_window(server)

    # ---- the comparison, once the window has closed and the server is gone
    def check(self, server, result: dict, observed) -> list:
        store, k, m = self.ctx.store, self.k, self.m
        compared = super().check(server, result, observed)
        got = observed.prom_delta
        differing = compared_bytes = unrecovered = 0
        if self.kept is not None and not self.sampled["missized"]:
            differing, compared_bytes, unrecovered = shell_jobs.compare_files(
                store, [os.path.join(server.data_dir, str(self.kept))], k, m,
                self.ctx.seed, self.ctx.pool_map, self.ctx.workers)
        good = result["conversions_started"] - result["conversions_failed"]
        kept_bytes = (k + m) * rs_codec.shard_size(store["dat_bytes"], k) if good else 0
        on_device = got(ENCODED_BYTES, backend="device") or 0
        elsewhere = (got(ENCODED_BYTES) or 0) - on_device
        compared += [
            ("compiles_inside_window", got(COMPILES) or 0, 0),
            ("conversions_failed", result["conversions_failed"], 0),
            ("conversions_behind", result["conversions_behind"], 0),
            ("shard_files_missized", self.sampled["missized"], 0),
            ("sampled_shard_blocks_differing", self.sampled["differing"], 0),
            ("shard_bytes_differing", differing, 0),
            ("shard_bytes_compared_short", max(0, kept_bytes - compared_bytes), 0),
            ("recovered_bytes_differing", unrecovered, 0),
            ("bytes_encoded_off_device", int(elsewhere), 0),
            ("bytes_uncounted_on_device", int(abs(on_device - result["converted_bytes"])), 0),
        ]
        if not self.ctx.rehearse:
            decodes_elsewhere = (got(DISPATCHES, op="decode") or 0) - (
                got(DISPATCHES, op="decode", backend="device") or 0)
            compared.append(("device_decode_share_short", decodes_elsewhere, 0))
        return compared


def sample_rows(rows: int, one_in: int, seed: int, vid: int) -> list:
    """A seeded `1/one_in` of a volume's rows (at least one), in order."""
    rng = np.random.default_rng([seed, 0x5A3, vid])
    return sorted(rng.choice(rows, max(1, rows // one_in), replace=False).tolist())
