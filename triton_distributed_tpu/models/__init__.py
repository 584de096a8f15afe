"""Model zoo + serving engine
(reference: `python/triton_dist/models/`)."""

from triton_distributed_tpu.models.base import ServedModel  # noqa: F401
from triton_distributed_tpu.models.config import ModelConfig  # noqa: F401
from triton_distributed_tpu.models.kv_cache import KVCache  # noqa: F401
from triton_distributed_tpu.models.qwen import Qwen3  # noqa: F401
from triton_distributed_tpu.models.glm4_moe_lite import (  # noqa: F401
    Glm4MoeLite)
from triton_distributed_tpu.models.engine import Engine  # noqa: F401


def AutoLLM(config, mesh, **kw):
    """Model registry (reference `AutoLLM`, `models/__init__.py`):
    dispatch on architecture name."""
    arch = (config.architecture or "qwen3").lower()
    if "qwen" in arch or "llama" in arch:
        return Qwen3(config, mesh, **kw)
    if "glm4_moe_lite" in arch or "glm4moelite" in arch:
        return Glm4MoeLite(config, mesh, **kw)
    if "solar_open2" in arch or "solaropen2" in arch:
        # bound here: the other families' set-up imports none of it
        from triton_distributed_tpu.models.solar_open2 import SolarOpen2
        return SolarOpen2(config, mesh, **kw)
    if "sdar_moe" in arch or "sdarmoe" in arch:
        from triton_distributed_tpu.models.sdar_moe import SdarMoe
        return SdarMoe(config, mesh, **kw)
    if "nemotron_h" in arch or "nemotronh" in arch:
        from triton_distributed_tpu.models.nemotron_h import NemotronH
        return NemotronH(config, mesh, **kw)
    if "cohere2_moe" in arch or "cohere2moe" in arch:
        from triton_distributed_tpu.models.cohere2_moe import Cohere2Moe
        return Cohere2Moe(config, mesh, **kw)
    if "smallthinker" in arch:
        from triton_distributed_tpu.models.smallthinker import SmallThinker
        return SmallThinker(config, mesh, **kw)
    raise ValueError(f"unknown architecture: {config.architecture}")
