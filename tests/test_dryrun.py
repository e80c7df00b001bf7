"""dryrun_multichip runs the mesh body (encode -> verify -> double-loss
reconstruct -> sharded lookup) on the devices JAX finds — here the 8
virtual CPU devices tests/conftest.py asks for."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import __graft_entry__ as graft


@pytest.mark.parametrize("n", [5, 8])
def test_dryrun_mesh_body_inline(n):
    """The full mesh body, including an awkward factorization
    (5 -> vol=5, blk=1)."""
    graft.dryrun_multichip(n)


def test_dryrun_raises_when_too_few_devices():
    with pytest.raises(RuntimeError, match="8 cpu device"):
        graft.dryrun_multichip(9)
