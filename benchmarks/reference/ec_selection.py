"""The yardstick of `ec.encode` without `-volumeId`: which volumes a pass of the
maintenance script converts. Upstream's rule, written down plainly
(weed/shell/command_ec_encode.go, collectVolumeIdsForEcEncode):

    a volume is selected when it is of the named collection,
    its last modification plus the quiet period lies before now, and
    its size is more than the given percentage of the master's volume size limit.

Both comparisons are strict, as upstream's are. Imports nothing of the program.
"""

from __future__ import annotations

MB = 1024 * 1024
UNITS = {"ns": 1e-9, "us": 1e-6, "ms": 1e-3, "s": 1.0, "m": 60.0, "h": 3600.0}


def duration_seconds(text: str) -> float:
    """Go's `time.ParseDuration` for what the flag takes: `1h`, `30m`, `45s`,
    `1h30m`, `1.5h`, `0`."""
    if text in ("0", "+0", "-0"):
        return 0.0
    total, rest = 0.0, text.lstrip("+")
    if not rest or rest.startswith("-"):
        raise ValueError(f"bad duration {text!r}")
    while rest:
        digits = 0
        while digits < len(rest) and (rest[digits].isdigit() or rest[digits] == "."):
            digits += 1
        unit = next((u for u in ("ns", "us", "ms", "s", "m", "h") if rest.startswith(u, digits)), None)
        if digits == 0 or unit is None:
            raise ValueError(f"bad duration {text!r}")
        total += float(rest[:digits]) * UNITS[unit]
        rest = rest[digits + len(unit):]
    return total


def select(volumes, collection: str, limit_mb: int, full_percent: float,
           quiet_seconds: float, now: float) -> list:
    """The ids, ascending, of the volumes `(id, collection, size, modified_at)`
    that one `ec.encode -collection <collection> -fullPercent=<p> -quietFor=<d>`
    converts at the time `now` (seconds), under a master whose limit is `limit_mb`."""
    quiet = int(quiet_seconds)  # upstream: int64(quietPeriod / time.Second)
    return sorted({
        vid for vid, coll, size, modified_at in volumes
        if coll == collection
        and modified_at + quiet < int(now)
        and float(size) > full_percent / 100 * float(limit_mb) * MB
    })
