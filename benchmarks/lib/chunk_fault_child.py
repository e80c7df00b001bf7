"""`server_child.py`, knowing one fault more: the launcher of the one child
that owns the chip, for `benchmarks/controls_chunks.py` and the tests under
benchmarks/tests/. A benchmark run never starts it.

`ec_interval_block_off`: every interval of a needle that lies in a small block
of data shard 5 (a healthy one in the cell) is read one block further down its
shard file, as a locate that is a row off would read it: right length, wrong
bytes, so the needle's CRC fails (or, in the shard's last row, the read comes
back short) and no GET that touches shard 5 gets its body.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks.lib import server_child  # noqa: E402

OFF_SHARD = 5


def fault_ec_interval_block_off() -> None:
    from seaweedfs_tpu.storage.erasure_coding.locate import Interval

    inner = Interval.to_shard_id_and_offset

    def off(self, large_block_size, small_block_size):
        shard, offset = inner(self, large_block_size, small_block_size)
        if shard == OFF_SHARD and not self.is_large_block:
            offset += small_block_size
        return shard, offset

    Interval.to_shard_id_and_offset = off


server_child.FAULTS["ec_interval_block_off"] = fault_ec_interval_block_off

if __name__ == "__main__":
    server_child.main()
