"""Shared helpers for the Pallas TPU kernels (flash_attention,
fused_cross_entropy): backend auto-detection and block-size fitting."""

from __future__ import annotations

import functools
import math

import jax


class KernelRefusedError(RuntimeError):
    """A Pallas kernel cannot run where it was asked to.  Names the
    kernel and carries the reason.  Raised today for one cause: the
    process has no TPU and was not explicitly put on the CPU, so the
    interpreter would silently stand in for Mosaic.  It is also the type
    to raise, with the compiler's message as the reason, for a variant
    Mosaic refuses — under libtpu 0.0.34 every variant compiles
    (``chip_smoke.py`` kernel leg), so no such refusal exists."""

    def __init__(self, kernel: str, reason: str):
        self.kernel = kernel
        self.reason = reason
        super().__init__(f"{kernel}: {reason}")


@functools.lru_cache(maxsize=None)
def _log_mode_once(interpret: bool, backend: str) -> None:
    from ..common import logging as bps_log

    bps_log.info("pallas kernels: %s (backend %s)",
                 "INTERPRET mode" if interpret else "Mosaic-compiled",
                 backend)


def resolve_interpret(interpret, kernel: str = "pallas kernel") -> bool:
    """None = by platform: compiled Mosaic kernels on a TPU backend, the
    Pallas interpreter ONLY when the process was explicitly put on the
    CPU (``JAX_PLATFORMS=cpu`` / ``jax_platforms`` — tests and virtual
    meshes).  A process that merely *landed* on the CPU because no
    accelerator was found raises: interpreting there would let every
    kernel "pass" on a machine that never compiled one."""
    if interpret is not None:
        return bool(interpret)
    backend = jax.default_backend()
    requested = (jax.config.jax_platforms or "").split(",")[0].strip()
    interpret = backend != "tpu"
    if interpret and not (backend == "cpu" and requested == "cpu"):
        raise KernelRefusedError(
            kernel,
            f"backend is {backend!r} but jax_platforms={requested!r}: no "
            f"TPU to compile for and the CPU was not requested explicitly. "
            f"Set JAX_PLATFORMS=cpu to run Pallas kernels in interpret "
            f"mode (tests, virtual meshes), or pass interpret=True")
    _log_mode_once(interpret, backend)
    return interpret


def fit_block(block: int, size: int, what: str = "dimension") -> int:
    """Largest usable block: min(block, size), reduced to a divisor of
    ``size`` (gcd) so sizes that worked at small defaults keep working at
    larger tuned defaults.  Degenerate sizes (divisor < 8 sublanes) are
    rejected.

    Block size dominates kernel throughput (an order of magnitude between
    block 8 and block 512 at the same shape), so a silent gcd fallback to
    a tiny block is a footgun: sizes whose resolved block is much smaller
    than requested warn with the padding remedy.  Sizes coprime to every
    usable block (e.g. GPT-2's 50257 vocab) raise — pad the dimension to
    a multiple of 128 (or pass an explicit dividing block) instead.
    """
    b = min(block, size)
    if size % b:
        b = math.gcd(size, b)
    if b < 8:
        raise ValueError(
            f"{what} {size} has no usable block (gcd with {block} is "
            f"{b} < 8); pad {what} to a multiple of 128 (or pass an "
            f"explicit block size dividing it)")
    if b * 4 <= min(block, size):
        from ..common import logging as bps_log

        bps_log.warning(
            "%s %d is indivisible by the requested block %d; falling back "
            "to block %d, which can cost substantial kernel throughput — "
            "pad %s to a multiple of 128 or pass an explicit block size",
            what, size, block, b, what)
    return b
