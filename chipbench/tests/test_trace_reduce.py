"""`trace_reduce` on a small trace recorded on the CPU (`data/`): two
windowed chunks of a sort program and a tanh-matmul program. The
expected numbers are counted by hand from the trace's events (listed in
the comments, in ns)."""
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from chipbench import trace_reduce as tr

DATA = Path(__file__).parent / "data" / "cpu_window.xplane.pb"

# chipbench.window: start 21381, length 4160036
LO, HI = 21381, 21381 + 4160036
# op intervals after merging (nested "end: ..." markers fall inside):
# sort 51428, sort 1191778, wait 109, tanh 49476, dot 139548, wait 76,
# sort 4488, sort 1925692, wait 191, tanh 73068, dot 177670, wait 84
BUSY = 3613608


@pytest.fixture(scope="module")
def trace():
    pd = ProfileData.from_file(str(DATA))
    # on the CPU the "device" ops run on the PjRt client's thread, and the
    # host's dispatch spans stand in for the module line
    (dev,) = tr.devices(
        pd, plane_match=lambda n: n == "/host:CPU",
        ops_line=lambda n: n.startswith("tf_XLAPjRtCpuClient"),
        modules_line=lambda n: n == "python")
    return dev, tr.host_events(pd)


def test_window(trace):
    _, host = trace
    assert tr.window(host, "chipbench.window") == (LO, HI)
    with pytest.raises(RuntimeError):
        tr.window(host, "no such span")


def test_busy_union_and_gaps(trace):
    dev, _ = trace
    assert tr.busy_ns(dev.ops, LO, HI) == BUSY
    gaps = tr.idle_gaps(dev.ops, LO, HI)
    assert sum(e - s for s, e in gaps) == (HI - LO) - BUSY == 546428
    # longest gap: window start to the first sort
    assert max(gaps, key=lambda g: g[1] - g[0]) == (LO, 289405)
    # a window that cuts the first sort in half
    assert tr.busy_ns(dev.ops, 289405 + 25714, 340833) == 25714


def test_kernel_sums(trace):
    dev, _ = trace
    # four sort events and four "end: sort" markers
    assert tr.matching_ns(dev.ops, LO, HI, ("sort",)) == 3176527
    assert tr.matching_ns(dev.ops, LO, HI, ("dot_general",)) == 318078
    assert tr.matching_ns(dev.ops, LO, HI, ("sort", "dot_general")) == \
        3176527 + 318078
    assert tr.matching_ns(dev.ops, LO, HI, ("no_such_kernel",)) == 0


def test_per_module_sums(trace):
    dev, _ = trace
    by_op = tr.sum_ns(dev.ops, LO, HI,
                      lambda n: None if n.startswith("end:") else n)
    assert by_op["sort.0"] == 51428 + 1191778 + 4488 + 1925692
    assert by_op["wrapped_tanh"] == 49476 + 73068
    assert by_op["dot_general.1"] == 139548 + 177670
    mods = tr.sum_ns(dev.modules, LO, HI, tr.module_name)
    assert mods["chipbench.train_steps"] == 1756762 + 2382558
    assert mods["chipbench.window"] == HI - LO
    assert tr.module_name("jit_train_step(42)") == "jit_train_step"
    assert tr.module_name("jit__build_batch") == "jit__build_batch"


def test_host_context(trace):
    _, host = trace
    # inside the first dispatch: the innermost of six spans
    assert tr.host_context(host, 155393) == \
        "CommonPjRtClient::CreateOutputs"
    assert tr.host_context(host, 160000) == "PjitFunction(<lambda>)"
    # between dispatches of the first chunk
    assert tr.host_context(host, 1607656) == "chipbench.train_steps"
    assert tr.host_context(host, 10) == "none"
