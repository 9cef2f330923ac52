"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any jax initialization).
Mesh construction goes through `repro.compat`, which builds every mesh
with all axes `Auto`.
"""

from __future__ import annotations

from repro import compat


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, pod: int | None = None):
    """Small mesh over host devices (tests / examples)."""
    if pod:
        return compat.make_mesh((pod, data, model), ("pod", "data", "model"))
    return compat.make_mesh((data, model), ("data", "model"))


def require_host_devices(n: int) -> None:
    """Fail fast, with the recipe, when fewer than n host devices exist.

    XLA fixes the device count at backend init, so this cannot be repaired
    from inside the process — callers that need a multi-shard host mesh
    (distributed churn, the subprocess tests) must set the flag first.
    """
    import jax

    have = jax.device_count()
    if have < n:
        raise RuntimeError(
            f"need {n} host devices, have {have}: set "
            f'XLA_FLAGS="--xla_force_host_platform_device_count={n}" '
            "before the first jax import"
        )


def make_zone_mesh(n_model: int, data: int = 1):
    """Mesh over the FIRST data*n_model host devices.

    Elastic membership (`repro.core.runtime.reshard`) runs meshes of
    several model-axis sizes in ONE process — each must build over a
    device prefix instead of the full device set, so a 4-device process
    can host the n_nodes=2 and n_nodes=4 topologies of one join/leave
    schedule side by side."""
    import jax

    require_host_devices(data * n_model)
    devs = jax.devices()[: data * n_model]
    return compat.make_mesh((data, n_model), ("data", "model"),
                            devices=devs)


def batch_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)
