"""Production mesh builders.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state -- dryrun.py must set XLA_FLAGS before the
first jax initialization.
"""
from __future__ import annotations

import os

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axes: the sharding rules place arrays
    with constraints and the partitioner propagates the rest, which the
    default Explicit axes of this JAX reject."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 = 256 chips per pod; 2x16x16 = 512 chips across 2 pods.

    The 'pod' axis is pure data parallelism (one cross-pod gradient
    all-reduce per step, DCN-friendly); 'data' is in-pod batch/FSDP; 'model'
    is tensor/expert parallelism confined to the pod's ICI domain.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(model: int = 1) -> jax.sharding.Mesh:
    """Tiny mesh over the real local devices (tests / examples)."""
    n = jax.device_count()
    return _make_mesh((n // model, model), ("data", "model"))


# Launcher-friendly aliases: dp -> batch parallelism, ep/tp -> the 'model'
# axis (tensor and expert parallelism share it; see parallel/sharding.py).
_MESH_AXIS_ALIASES = {"dp": "data", "ep": "model", "tp": "model"}


def mesh_spec_sizes(spec: str) -> tuple:
    """Parse 'dp=2,ep=2' -> ((axis, size), ...) WITHOUT touching jax device
    state -- launchers call this to set XLA_FLAGS before the first jax use."""
    out = []
    for part in spec.split(","):
        k, sep, v = part.partition("=")
        if not sep:
            raise ValueError(f"bad mesh spec {spec!r}: expected name=size pairs")
        out.append((_MESH_AXIS_ALIASES.get(k.strip(), k.strip()), int(v)))
    names = [n for n, _ in out]
    if len(set(names)) != len(names):
        raise ValueError(
            f"mesh spec {spec!r} maps two names onto one axis "
            f"(aliases: {_MESH_AXIS_ALIASES})"
        )
    return tuple(out)


def parse_mesh_spec(spec: str) -> jax.sharding.Mesh:
    """'dp=2,ep=2' (aliases dp->data, ep/tp->model) -> a live Mesh."""
    pairs = mesh_spec_sizes(spec)
    return _make_mesh(
        tuple(s for _, s in pairs), tuple(n for n, _ in pairs)
    )


# One fixed, git-ignored directory in the checkout: the cache's path is part
# of what a later process looks up, so it must not move between runs.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache; returns its directory.

    Cold-start compile time is a serving SLO: a staged engine compiles the
    decode tick plus O(log chunk) prefill shapes on boot, all byte-stable
    for a fixed model + mesh, so a warm disk cache turns the second boot's
    compiles into reads.  An operator-set ``JAX_COMPILATION_CACHE_DIR`` is
    jax's own setting and wins: nothing is set in code then.  Otherwise the
    cache lives at ``COMPILE_CACHE_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def preinit_mesh_flag(argv) -> None:
    """Give a CPU-only ``--mesh`` run its virtual devices.

    Only when ``JAX_PLATFORMS=cpu`` is set (a run pinned to the host, such
    as a test) and the operator did not set XLA_FLAGS themselves: scans
    ``argv`` for ``--mesh SPEC`` or ``--mesh=SPEC`` and sets
    ``--xla_force_host_platform_device_count`` to the mesh size.  On an
    accelerator the mesh is built from the real devices.  Call before the
    first jax initialization (importing this module is safe: the flag is
    read at backend-client creation, not import).  A malformed spec raises
    here, before jax starts."""
    if os.environ.get("JAX_PLATFORMS") != "cpu" or "XLA_FLAGS" in os.environ:
        return
    spec = None
    for i, arg in enumerate(argv):
        if arg == "--mesh" and i + 1 < len(argv):
            spec = argv[i + 1]
            break
        if arg.startswith("--mesh="):
            spec = arg[len("--mesh="):]
            break
    if spec is None:
        return
    n = 1
    for _, size in mesh_spec_sizes(spec):
        n *= size
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n}"
    )
