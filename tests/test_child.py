"""``tests/_child.py``: the child-process runner behind the multi-device
tests sets the forced host device count, and fails a hung or crashed
child quickly, naming the test; a child that checks several meshes
passes or fails each mesh on its own line."""

import time

import pytest

from _child import assert_mesh_ok, run_child


def test_run_child_forces_host_devices():
    out = run_child("""
        import jax
        print(len(jax.devices()))
    """, devices=4, timeout=60)
    assert out.split() == ["4"]


def test_run_child_timeout_names_the_test():
    t0 = time.monotonic()
    with pytest.raises(pytest.fail.Exception) as e:
        run_child("""
            import time
            print("child started", flush=True)
            time.sleep(60)
        """, devices=1, timeout=5)
    assert time.monotonic() - t0 < 10
    msg = str(e.value)
    assert "test_run_child_timeout_names_the_test" in msg
    assert "after 5 s" in msg and "child started" in msg


def test_run_child_failure_names_the_test():
    with pytest.raises(AssertionError) as e:
        run_child("raise SystemExit('child gave up')", devices=1, timeout=60)
    msg = str(e.value)
    assert "test_run_child_failure_names_the_test" in msg
    assert "child gave up" in msg


def test_assert_mesh_ok_reads_only_its_own_mesh_line():
    out = ("OVERLAP_PARITY_OK 2 1\n"
           "PARITY_OK 1 1\n"
           "PARITY_FAIL 4 2 Not equal to tolerance rtol=2e-05\n")
    assert_mesh_ok(out, "PARITY", 1, 1)
    with pytest.raises(AssertionError, match=r"mesh \(4, 2\)"):
        assert_mesh_ok(out, "PARITY", 4, 2)
    with pytest.raises(AssertionError, match=r"mesh \(2, 1\)"):
        assert_mesh_ok(out, "PARITY", 2, 1)
