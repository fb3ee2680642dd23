"""Places name devices the process can see, or raise: an explicit TPU
place on a CPU-only host must never resolve to a CPU device."""
import jax
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework import place as place_mod


def test_tpu_place_raises_without_a_tpu():
    assert jax.devices()[0].platform == "cpu"  # the suite's platform
    assert not paddle.is_compiled_with_tpu()
    with pytest.raises(RuntimeError, match="0 tpu device"):
        paddle.TPUPlace(0).jax_device()
    with pytest.raises(RuntimeError, match="0 tpu device"):
        paddle.CUDAPlace(0).jax_device()


def test_set_device_tpu_raises_and_keeps_the_current_place():
    before = paddle.get_device()
    for name in ("tpu", "tpu:1", "gpu"):
        with pytest.raises(RuntimeError, match="tpu device"):
            paddle.set_device(name)
    with pytest.raises(RuntimeError):
        paddle.set_device(paddle.TPUPlace(0))
    assert paddle.get_device() == before


def test_cpu_place_and_default_place_resolve():
    assert paddle.CPUPlace().jax_device().platform == "cpu"
    assert paddle.set_device("cpu") == paddle.CPUPlace()
    # with no accelerator the default place is the CPU, chosen not forced
    assert isinstance(place_mod._default_place(), paddle.CPUPlace)
    with pytest.raises(ValueError):
        paddle.set_device("warp-drive")
