"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module touches no JAX device state. Single pod = 256 chips
(16x16 data x model); multi-pod adds a leading 2-way ``pod`` axis (512).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_local_mesh", "parse_mesh_spec",
           "mesh_from_spec"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = 1
    for s in shape:
        need *= s
    devs = jax.devices()
    assert len(devs) >= need, (
        f"need {need} devices (set XLA_FLAGS=--xla_force_host_platform_"
        f"device_count=512 before importing jax); have {len(devs)}")
    return jax.make_mesh(shape, axes, devices=devs[:need],
                         axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over the locally available devices (tests / CPU runs)."""
    n = len(jax.devices())
    assert data * model <= n, f"need {data * model} devices, have {n}"
    return jax.make_mesh((data, model), ("data", "model"),
                         devices=jax.devices()[:data * model],
                         axis_types=(AxisType.Auto,) * 2)


def parse_mesh_spec(spec: str) -> dict:
    """``"data=2,model=4"`` -> ``{"data": 2, "model": 4}``. The CLI surface
    for serving meshes (``--mesh``); unknown axes are rejected so a typo
    can't silently serve unsharded."""
    out = {"data": 1, "model": 1}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        try:
            axis, _, val = part.partition("=")
            n = int(val)
        except ValueError:
            raise ValueError(f"bad mesh spec part {part!r} in {spec!r} "
                             f"(expected axis=N)") from None
        if axis not in out:
            raise ValueError(f"unknown mesh axis {axis!r} in {spec!r} "
                             f"(serving meshes have data/model)")
        assert n >= 1, (axis, n)
        out[axis] = n
    return out


def mesh_from_spec(spec):
    """``--mesh`` string to a local serving mesh; None/empty/1x1 -> None
    (the single-device engine path, no mesh context anywhere)."""
    if not spec:
        return None
    axes = parse_mesh_spec(spec) if isinstance(spec, str) else dict(spec)
    if axes.get("data", 1) == 1 and axes.get("model", 1) == 1:
        return None
    return make_local_mesh(data=axes["data"], model=axes["model"])
