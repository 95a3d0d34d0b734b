"""The driver's compile-check and multi-chip dry run must always work."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_entry_compiles():
    import jax

    import __graft_entry__ as ge

    fn, args = ge.entry()
    parity, digests = jax.jit(fn)(*args)
    assert parity.shape == (2, 2, 1024)
    assert digests.shape == (2, 6, 32)


def test_dryrun_multichip_8(capsys):
    import __graft_entry__ as ge

    ge.dryrun_multichip(8)
    out = capsys.readouterr().out
    # the result line names what it ran on: here, the CPU lane's 8
    # virtual devices — never to be read as a chip run
    assert "dryrun_multichip OK: platform=cpu" in out, out
    assert "CPU rehearsal" in out and "8 devices" in out, out
