"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel ships three ways (see ops.py): a sequential-semantics oracle
(ref.py), a vectorized jnp implementation, and the Pallas kernel proper
(pl.pallas_call + BlockSpec VMEM tiling; interpreted off the TPU).

  hash_probe       blocked open-addressing insert/find (DHashMap)
  bloom_kernel     blocked Bloom hashing + membership
  binning          destination histogram (exchange engine / ISx)
  flash_attention  fused online-softmax attention (LM hot spot)
"""

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def platform() -> str:
    """The platform kernels are built for; the one place that asks JAX.

    ``interpret_mode`` and ``ops.default_impl`` both derive from it, so a
    test that compiles for a described (not attached) TPU steers every
    kernel decision by patching this one function.
    """
    return jax.default_backend()


def interpret_mode(vma: frozenset = frozenset()):
    """``interpret=`` argument for ``pl.pallas_call`` on this platform.

    False on TPU (Mosaic compiles the kernel).  Elsewhere a kernel whose
    operands vary over manual mesh axes (``vma``, inside a checked
    ``jax.shard_map``) runs in the TPU interpreter, which types the
    kernel body's constants with those axes; the plain HLO interpreter
    cannot, but is several times faster, so it runs the rest.
    """
    if platform() == "tpu":
        return False
    return pltpu.InterpretParams() if vma else True


def pallas_call(kernel, *, out_shape, **kw):
    """``pl.pallas_call`` typed for ``jax.shard_map``.

    Every output carries the union of the operands' varying manual axes
    (``vma``), and operands that vary over fewer axes are cast up to that
    union, so a kernel traces inside a ``shard_map`` with ``check_vma``
    on.  Outside any ``shard_map`` the union is empty and nothing changes.
    """
    def call(*args):
        vmas = [jax.typeof(a).vma for a in args]
        vma = frozenset().union(*vmas)
        args = [a if v == vma else
                jax.lax.pcast(a, tuple(sorted(vma - v)), to="varying")
                for a, v in zip(args, vmas)]
        typed = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, vma=vma),
            out_shape)
        return pl.pallas_call(kernel, out_shape=typed,
                              interpret=interpret_mode(vma), **kw)(*args)
    return call


from repro.kernels import ops, ref  # noqa: E402,F401
