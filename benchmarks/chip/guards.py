"""What a run must find before its numbers mean anything: a TPU with the
chips the cell asks for, the compiled Pallas kernel as the default backend,
and no hidden fallback while it serves.

These are the checks of the repository's bring-up smoke test, kept here so
that a change to the program cannot change what the benchmark demands.
"""
from __future__ import annotations

PLATFORM, KERNEL = "tpu", "pallas"


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def open_chip(n_chips: int):
    """The devices of the run; raises :class:`NoChip` where JAX finds no
    TPU or fewer than ``n_chips`` of them. Never falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != PLATFORM:
        raise NoChip(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < n_chips:
        raise NoChip(f"the cell asks for {n_chips} chips, JAX sees "
                     f"{len(devs)}")
    return devs


def kernel_faults(bk) -> list:
    """Why the default backend would not run the compiled kernel: empty
    where it is ``pallas`` with ``interpret=False``."""
    out = []
    if bk.default_backend_name() != KERNEL:
        out.append(f"default backend is {bk.default_backend_name()!r}")
    be = bk.get_backend(KERNEL)
    if be._interpret is not False or bk.pallas_interpret_default():
        out.append("the pallas backend would interpret the kernel")
    return out


def hidden_fallbacks(before: dict, after: dict, dispatch_log,
                     expect_backend: str) -> int:
    """Dispatches that did not run where they should have: resilience
    fallbacks, retries, failures and salvaged rows; rows on the host
    oracle; any dispatch logged on another backend or as degraded.
    ``before``/``after`` are counter snapshots around the window."""
    def delta(key):
        return after.get(key, 0) - before.get(key, 0)

    def labeled(prefix):
        return sum(v - before.get(k, 0) for k, v in after.items()
                   if k.startswith(prefix + "{"))

    n = (delta("resilience.fallbacks") + delta("resilience.retries")
         + delta("resilience.salvaged_rows")
         + labeled("resilience.dispatch_failures")
         + delta("backend.run_rows{backend=oracle}"))
    n += sum(1 for d in dispatch_log
             if d["backend"] != expect_backend or d.get("degraded"))
    return int(n)
