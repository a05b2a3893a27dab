"""Assigned-architecture model zoo (scan-over-layers JAX stacks)."""

from repro.models.model_zoo import ModelApi, TensorSpec, build

__all__ = ["ModelApi", "TensorSpec", "build"]
