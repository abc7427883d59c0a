"""Scheduler kernels: the host level's bin packing (``pack``) and the
LocalSearch candidate-move sweep as a Pallas kernel (``move_eval``), with
its dispatch wrappers (``ops``) and pure-jnp oracles (``ref``)."""
from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
