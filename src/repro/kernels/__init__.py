"""Pallas TPU kernels of the local SpGEMM compute (oracles in ``ref``).

Every kernel runs compiled (Mosaic) on a TPU and through the Pallas
interpreter on the CPU; ``resolve_interpret`` is the one place that choice
is made.
"""
from __future__ import annotations


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Interpret mode for the platform jax runs on.

    ``None`` means the platform's mode: the interpreter on CPU, the compiled
    Mosaic kernel on TPU.  An explicit ``True`` on a TPU is refused, so no
    caller runs the interpreter where the compiled kernel exists.
    """
    import jax

    platform = jax.default_backend()
    if platform not in ("cpu", "tpu"):
        raise RuntimeError(
            f"the Pallas kernels run on TPU (compiled) or CPU (interpreted), "
            f"not on {platform!r}"
        )
    if interpret is None:
        return platform == "cpu"
    if interpret and platform == "tpu":
        raise ValueError(
            "interpret mode requested on a TPU; the compiled kernel runs there "
            "(pass interpret=None or backend='pallas')"
        )
    return bool(interpret)
