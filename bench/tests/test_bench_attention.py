"""The ``attention_kernel_ms`` reader on synthesized traces."""

from types import SimpleNamespace

import pytest

from benchkit import BENCH
from bench import tracing
from bench.harness import load_module

MS = 1e6


@pytest.fixture(scope="module")
def read():
    return load_module(BENCH / "metrics" / "attention_kernel_ms.py",
                       "attention_kernel_ms").read


def _ctx(devices, updates=2):
    return SimpleNamespace(trace=tracing.Trace(devices, []),
                           window=(0, 10 * MS), updates=updates)


def test_sums_the_three_kernels_over_chips_per_update(read):
    chip0 = [tracing.Event("flash_attention_fwd.3", 0, 3 * MS),
             tracing.Event("transpose_jvp_jit_flash_attention_dq___.2",
                           3 * MS, 4 * MS),
             tracing.Event("flash_attention_dkv.7", 4 * MS, 6 * MS),
             tracing.Event("fusion.1228", 6 * MS, 9 * MS)]
    chip1 = [tracing.Event("flash_attention_fwd.3", 9 * MS, 12 * MS)]
    # 6 ms on chip 0 and 1 ms inside the window on chip 1, over 2 updates
    assert read(_ctx({"/device:TPU:0": chip0, "/device:TPU:1": chip1})) == \
        pytest.approx(3.5)


@pytest.mark.parametrize("events,updates", [
    ([tracing.Event("fusion.1228", 0, 3 * MS)], 2),        # row-block path
    ([tracing.Event("flash_attention_fwd.1", 0, MS)], 0),  # no update
])
def test_none_without_the_kernels_or_updates(read, events, updates):
    assert read(_ctx({"/device:TPU:0": events}, updates)) is None
