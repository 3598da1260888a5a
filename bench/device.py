"""What the benchmark asks of the devices: the chip check, the mesh,
sharded inputs, argument shapes, the transport a traffic file names,
and peak memory."""

from __future__ import annotations


class NoChip(RuntimeError):
    """JAX finds no TPU, fewer chips than the cell asks for, or Pallas
    would interpret its kernels."""


def require_chip(chips: int):
    """The first ``chips`` TPU devices; raises :class:`NoChip` otherwise.
    The benchmark never falls back to the CPU."""
    import jax
    from repro.kernels import interpret_mode

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds "
                     f"{len(devices)}")
    if interpret_mode():
        raise NoChip("Pallas kernels would run in interpret mode")
    return devices[:chips]


def make_mesh(devices):
    from repro.compat import make_mesh as _make_mesh
    return _make_mesh((len(devices),), ("bcl",), devices=devices)


def sharded(mesh, x):
    """``x`` split along its first axis over the mesh's ``bcl`` axis."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    return jax.device_put(x, NamedSharding(mesh, P("bcl")))


def shapes_of(args):
    """Shapes, types and shardings of a program's arguments, so that
    the program can be lowered again for its compiled text."""
    import jax
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=x.sharding), args)


def transport_of(name: str):
    """``dense`` (the default transport) or ``hier:<pr>x<pc>``."""
    from repro.core import HierarchicalTransport
    if name == "dense":
        return None
    if name.startswith("hier:"):
        pr, pc = (int(v) for v in name[5:].split("x"))
        return HierarchicalTransport(pr, pc)
    raise ValueError(f"unknown transport {name!r}")


def memory_peak_bytes(devices) -> int | None:
    """Peak bytes in use on the fullest device, where JAX reports it."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None
