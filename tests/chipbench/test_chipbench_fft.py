"""The reader of the 2-D DFT kernel's device time, on synthetic traces,
and its declaration in BENCHMARK.json."""

import json
from types import SimpleNamespace

import pytest

from conftest import REPO

from chipbench import bench
from chipbench import trace as tr
from chipbench.metrics import fft_kernel_device_ms

CELLS = ["nlinv-paper768-1chip.service-k2", "nlinv-paper768-1chip.stream-k1",
         "nlinv-paper768-coil4.service-k2", "nlinv-paper768-1chip.service-k4"]


def _ctx(ops, frames=2):
    trace = tr.Trace(ops, {}, [tr.Event("chipbench.window", 0.0, 1e9)])
    return SimpleNamespace(trace=trace, frames=frames, chips=len(ops))


def test_reads_the_kernels_time_per_frame_and_chip():
    """2 frames on 2 chips, 10 kernel calls of 0.4 ms on each chip; other
    kernels and fusions are not counted."""
    def chip(t0):
        evs = [tr.Event(f"dft2c_pallas.{i % 3}", t0 + 1e6 * i, 4e5)
               for i in range(10)]
        return evs + [tr.Event("fusion.12", t0 + 2e7, 5e6),
                      tr.Event("coil_lincomb_pallas.3", t0 + 3e7, 1e6)]
    ctx = _ctx({0: chip(0.0), 1: chip(1e8)})
    assert fft_kernel_device_ms.read(ctx) == pytest.approx(10 * 0.4 / 2)


def test_nothing_to_read_without_the_kernel():
    """A program on XLA's FFT (the parent of the kernel), or no trace."""
    ctx = _ctx({0: [tr.Event("fusion.7", 0.0, 1e6),
                    tr.Event("copy.3", 2e6, 1e6)]})
    assert fft_kernel_device_ms.read(ctx) is None
    assert fft_kernel_device_ms.read(SimpleNamespace(trace=None, frames=2,
                                                     chips=1)) is None


def test_declared_for_the_single_slice_cells():
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    m = next(m for m in b["per_layer"] if m["name"] == "fft_kernel_device_ms")
    assert (m["source"], m["layer"], m["moves"]) == (
        "device_trace", "library", "frames_per_s")
    assert m["workloads"] == CELLS
    for cell in CELLS:
        loaded = bench.load_cell(REPO, cell)
        assert "fft_kernel_device_ms" in {x["name"] for x in loaded.per_layer}
    bench.load_reader(REPO, "fft_kernel_device_ms")
