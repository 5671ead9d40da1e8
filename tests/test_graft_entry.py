"""Driver entry-point gates: ``entry()`` compiles, and the multichip dry
run runs in-process on the live backend — raising, never degrading, when
the backend is too small."""

import pytest

import __graft_entry__ as graft


def test_dryrun_multichip_in_process():
    # conftest provides 8 virtual CPU devices
    graft.dryrun_multichip(8)


def test_dryrun_multichip_raises_on_too_few_devices():
    with pytest.raises(RuntimeError, match=r"has 8 device\(s\)"):
        graft.dryrun_multichip(16)


def test_entry_forward_compiles():
    import jax
    fn, args = graft.entry()
    out = jax.jit(fn)(*args)
    assert out.shape == (64, 4)
