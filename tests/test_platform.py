"""The device peaks table (utils/platform.DEVICE_SPECS): one table keyed by
the device_kind string the chip reports; an unknown TPU kind is an error,
never a default."""
import types

import pytest

from transmogrifai_tpu.utils import platform as P


def test_knows_the_recorded_v5e_kind():
    # 'TPU v5 lite' is what jax.devices()[0].device_kind reported on the
    # TPU v5e the chip runs used (CHANGES.md, PR 21)
    spec = P.device_spec("TPU v5 lite")
    assert spec.bf16_flops == 197e12 and spec.int8_ops == 393e12
    assert spec.hbm_bytes_per_s == 819e9 and spec.hbm_bytes == 16e9
    assert "TPU v5e" in spec.source


def test_unknown_tpu_kind_raises(monkeypatch):
    with pytest.raises(LookupError, match="TPU v9 imaginary"):
        P.device_spec("TPU v9 imaginary")
    # ... also when the kind is read off the device itself
    import jax
    fake = types.SimpleNamespace(platform="tpu",
                                 device_kind="TPU v9 imaginary")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    with pytest.raises(LookupError):
        P.device_spec()


def test_cpu_has_no_roof():
    assert P.device_spec() is None
