"""jit'd dispatch wrappers: one entry point per kernel, impl-selectable.

``impl="xla"``    — pure-jnp path (the solver's own path, and the oracle)
``impl="pallas"`` — Pallas TPU kernel, compiled for the TPU the process
                    runs on.  Off a TPU it raises unless the caller asks for
                    the interpreter explicitly with ``interpret=True`` (the
                    kernel tests do); nothing falls back to interpret mode
                    on its own.

The backend is read per call, never at import: importing this module must
not start a JAX backend (and with it claim the chip).
"""
from __future__ import annotations

import jax

from repro.kernels import ref as _ref
from repro.kernels.move_eval import move_eval_best_pallas as _move_best_pallas
from repro.kernels.move_eval import move_eval_pallas as _move_pallas


def _pallas_interpret(interpret: bool) -> bool:
    """The ``interpret`` flag for a Pallas call, checked against the backend."""
    if interpret:
        return True
    backend = jax.default_backend()
    if backend != "tpu":
        raise RuntimeError(
            f'impl="pallas" needs a TPU (the default backend is {backend!r}); '
            "pass interpret=True to run the kernel in the Pallas interpreter")
    return False


def move_eval(*args, impl: str = "xla", interpret: bool = False):
    """delta[N, T] — see core.delta.move_delta_cost for the signature."""
    if impl == "xla":
        return _ref.move_eval_ref(*args)
    return _move_pallas(*args, interpret=_pallas_interpret(interpret))


def move_eval_best(*args, impl: str = "xla", interpret: bool = False):
    """Fused sweep + move-mask + per-app argmin -> (best_score[N], best_tier[N]).

    The reduction the batched top-k LocalSearch consumes (it only ever looks
    at the top-k of the N per-app best scores); see
    core.delta.move_best_per_app for the signature and mask semantics.
    """
    if impl == "xla":
        return _ref.move_eval_best_ref(*args)
    return _move_best_pallas(*args, interpret=_pallas_interpret(interpret))
