"""Roofline ceilings come from the published-peaks table, keyed by the
device kind JAX reports; an accelerator missing from it is an error."""
from __future__ import annotations

from types import SimpleNamespace

import jax
import pytest

from repro.roofline import hw


def _fake_devices(monkeypatch, platform: str, kind: str) -> None:
    dev = SimpleNamespace(platform=platform, device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [dev])


def test_v5e_peaks_from_table(monkeypatch):
    _fake_devices(monkeypatch, "tpu", "TPU v5 lite")
    prof = hw.machine_profile()
    assert prof == {"name": "tpu-v5e", "peak_flops": 197e12,
                    "mem_bw": 819e9}
    prof["peak_flops"] = 0.0                 # a copy, not the table
    assert hw.DEVICE_PEAKS["TPU v5 lite"]["peak_flops"] == 197e12


@pytest.mark.parametrize("platform,kind", [("tpu", "TPU v4"),
                                           ("gpu", "NVIDIA H100")])
def test_unknown_accelerator_raises(monkeypatch, platform, kind):
    _fake_devices(monkeypatch, platform, kind)
    with pytest.raises(KeyError, match=kind):
        hw.machine_profile()
