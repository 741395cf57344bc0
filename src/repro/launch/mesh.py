"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state. The single-pod mesh is 16x16 = 256 chips
(data, model); the multi-pod mesh is 2x16x16 = 512 chips (pod, data, model),
where the ``pod`` axis composes with ``data`` for batch sharding — the
paper's optional multi-rack 800 GbE expansion (Section 17.1) maps to the
pod axis' DCN-class links.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes, devices=None):
    """jax.make_mesh with every axis Auto (GSPMD-partitioned), over
    ``devices`` when given (e.g. a described TPU topology's devices)."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Degenerate mesh over however many devices exist (CPU tests)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return make_mesh((n // model_parallel, model_parallel), ("data", "model"))


def force_host_device_count(n: int):
    """Ask XLA for ``n`` host (CPU) devices, the CI/laptop stand-in for a
    real accelerator mesh.

    Sets (or raises) ``--xla_force_host_platform_device_count`` in
    ``XLA_FLAGS``; jax reads the flag at backend init, so this must run
    before the first device query. The function SELF-VERIFIES by counting
    devices afterwards (initializing the backend, which the caller's mesh
    construction was about to do anyway): if the count still falls short —
    the backend was already up when we were called — it raises the
    actionable set-it-before-starting error instead of letting mesh
    construction fail with an opaque shape mismatch.
    """
    import os
    import re
    if n <= 1:
        return
    prev = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", prev)
    if m is None:
        os.environ["XLA_FLAGS"] = (
            prev + f" --xla_force_host_platform_device_count={n}").strip()
    elif int(m.group(1)) < n:
        # a smaller inherited count would make the requested mesh unbuildable
        os.environ["XLA_FLAGS"] = prev.replace(
            m.group(0), f"--xla_force_host_platform_device_count={n}")
    if len(jax.devices()) < n:
        raise RuntimeError(
            f"jax already initialized with {len(jax.devices())} device(s); "
            f"set XLA_FLAGS=--xla_force_host_platform_device_count={n} in "
            "the environment before starting the process")


def parse_mesh_shape(spec: str) -> tuple[int, int]:
    """'4x2' -> (data=4, model=2); 'auto' splits the available devices into
    (n // m, m) with the model axis as wide a power of two as divides n
    (capped at 8 — serving TP beyond 8-way wants a real topology choice)."""
    if spec == "auto":
        n = len(jax.devices())
        m = 1
        while m < 8 and n % (m * 2) == 0:
            m *= 2
        return n // m, m
    d, _, m = spec.partition("x")
    return int(d), int(m)


def make_serving_mesh(spec: str = "auto"):
    """(data, model) host mesh for ``ServeEngine(..., mesh=...)``."""
    data, model = parse_mesh_shape(spec)
    return make_mesh((data, model), ("data", "model"))
