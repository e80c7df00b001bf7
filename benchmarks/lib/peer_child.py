"""The launcher of a peer volume server with a fault planted, for
`benchmarks/controls_spread.py` and the tests under benchmarks/tests/: a
benchmark run starts its peers as `python -m seaweedfs_tpu volume ...` and
never comes through here.

    python benchmarks/lib/peer_child.py --fault ec_span_byte -- volume -port ...

It plants the fault, then runs the module `seaweedfs_tpu` as `__main__` with
the arguments after `--`, as benchmarks/lib/server_child.py does for the
server that holds the chip. A peer is started with `JAX_PLATFORMS=cpu`.
"""

from __future__ import annotations

import argparse
import runpy
import sys


def fault_ec_span_byte() -> None:
    """One byte in 256 of every shard span this server reads for another
    (`VolumeEcShardRead` reads through `EcVolumeShard.read_at`) altered: a
    survivor that answers the right length with the wrong bytes. The bit that
    is flipped follows the shard's id: the same bit flipped in every remote
    survivor of a 4/4/3/3 deal cancels in the decode (the all-ones vector is a
    codeword of this matrix, and the reference codec reads 0 bytes wrong for
    every such deal), which would make the control prove nothing."""
    from seaweedfs_tpu.storage.erasure_coding.ec_volume import EcVolumeShard

    inner = EcVolumeShard.read_at

    def altered(self, size, offset):
        span = bytearray(inner(self, size, offset))
        flip = 1 << (self.shard_id % 8)
        span[::256] = bytes(b ^ flip for b in span[::256])
        return bytes(span)

    EcVolumeShard.read_at = altered


FAULTS = {"ec_span_byte": fault_ec_span_byte}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fault", choices=sorted(FAULTS), required=True)
    ap.add_argument("peer_args", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    FAULTS[args.fault]()
    sys.argv = ["seaweedfs_tpu", *[a for a in args.peer_args if a != "--"]]
    runpy.run_module("seaweedfs_tpu", run_name="__main__", alter_sys=True)


if __name__ == "__main__":
    main()
