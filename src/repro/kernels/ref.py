"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

Each oracle is the solver's own XLA path, so the kernel tests pin the
Pallas implementations to the exact semantics the solver uses.
"""
from repro.core.delta import move_best_per_app as move_eval_best_ref
from repro.core.delta import move_delta_cost as move_eval_ref

__all__ = ["move_eval_ref", "move_eval_best_ref"]
