"""Configuration of the suffix-array system: plain frozen dataclasses."""
from repro.config.base import SAConfig, SuperblockConfig, asdict, replace

__all__ = ["SAConfig", "SuperblockConfig", "asdict", "replace"]
