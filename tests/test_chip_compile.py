"""Compile rehearsals for a TPU v5e chip, at real widths, with no chip.

The TPU compiler is installed with JAX and compiles for a described, not
attached, ``v5e:2x2`` topology.  These tests compile every Pallas kernel and
the granite-3-2b serving steps for one chip of it, so that what the chip's
compiler refuses (a block that breaks the (8, 128) tiling rule, a primitive
Mosaic cannot lower, a program that does not fit the chip's 16 GB) fails
here, before any chip time is spent.  The served decode step is also
checked for a copy of the whole stacked cache (at the serving cells'
shapes, and on the CPU at a tiny size).  Nothing runs: a pass says nothing
of results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
every test file.  Keep all such compiles in this one file.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.attention.ops import flash_attention
from repro.kernels.quantize.ops import dequantize, quantize
from repro.kernels.ssd.ops import ssd_scan
from repro.launch.steps import (batch_specs, cache_specs,
                                make_greedy_decode_step,
                                make_greedy_prefill_step, params_specs)

HBM_BYTES = 16e9            # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _kernel_compiles(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    # the Mosaic kernel itself, not the interpreter's emulation of it
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


BF16, F32, I8 = jnp.bfloat16, jnp.float32, jnp.int8


@pytest.mark.parametrize("causal,s", [(True, 4096), (False, 4000)],
                         ids=["causal-gqa", "padded-noncausal"])
def test_flash_attention_compiles(one_chip, causal, s):
    # granite-3-2b attention: 32 query heads over 8 kv heads, head dim 64
    _kernel_compiles(functools.partial(flash_attention, causal=causal),
                     one_chip, ((1, s, 32, 64), BF16), ((1, s, 8, 64), BF16),
                     ((1, s, 8, 64), BF16))


def test_ssd_compiles(one_chip):
    # mamba2-1.3b: 64 heads of width 64, state 128
    b, s, h, p, n = 1, 4096, 64, 64, 128
    _kernel_compiles(ssd_scan, one_chip, ((b, s, h, p), F32), ((b, s, h), F32),
                     ((h,), F32), ((b, s, n), F32), ((b, s, n), F32))


def test_quantize_compiles(one_chip):
    _kernel_compiles(quantize, one_chip, ((4096, 2048), BF16))


def test_dequantize_compiles(one_chip):
    _kernel_compiles(dequantize, one_chip, ((4096, 2048), I8), ((16, 8), F32))


def _fits(compiled):
    ma = compiled.memory_analysis()
    used = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert used < HBM_BYTES, f"{used / 1e9:.2f} GB does not fit one chip"
    return used


def test_granite_full_prefill_compiles_and_fits(one_chip):
    # the default attn_impl: serving prefill writes a cache, which keeps it
    # off the flash kernel whatever attn_impl says (models/layers.py _sdpa)
    cfg = get_config("granite-3-2b", "full")
    b, prompt, max_len = 4, 512, 1024
    step = jax.jit(make_greedy_prefill_step(cfg), donate_argnums=(2,))
    compiled = step.lower(
        _on(one_chip, params_specs(cfg)),
        _on(one_chip, batch_specs(cfg, b, prompt)),
        _on(one_chip, cache_specs(cfg, b, max_len))).compile()
    assert _fits(compiled) > 5e9          # the bf16 weights alone are ~5 GB


def test_granite_full_decode_compiles_and_fits(one_chip):
    cfg = get_config("granite-3-2b", "full")
    b, max_len = 4, 1024
    step = jax.jit(make_greedy_decode_step(cfg), static_argnums=(3,),
                   donate_argnums=(2,))
    compiled = step.lower(
        _on(one_chip, params_specs(cfg)),
        jax.ShapeDtypeStruct((b, 1), jnp.int32, sharding=one_chip),
        _on(one_chip, cache_specs(cfg, b, max_len)), max_len).compile()
    assert _fits(compiled) > 5e9


def _leaf_copies(hlo, cache):
    """The copies in compiled HLO text whose result has the shape of a
    stacked cache leaf that holds rows or states (the int32 len counters,
    one per layer and slot, are left out)."""
    shapes = {a.shape for a in jax.tree.leaves(cache) if a.dtype != jnp.int32}
    return [name for name, dims in
            re.findall(r"%(\S+) = \w+\[([\d,]*)\]\S* copy\(", hlo)
            if tuple(int(d) for d in dims.split(",") if d) in shapes]


def _served_decode(cfg, slots, rows, bucket, sharding=None):
    """The engine's decode step compiled for ``slots`` x ``rows`` at kv
    bucket ``bucket`` -> (compiled, cache shapes)."""
    def on(tree):
        return tree if sharding is None else _on(sharding, tree)

    step = jax.jit(make_greedy_decode_step(cfg), static_argnums=(3,),
                   donate_argnums=(2,))
    cache = cache_specs(cfg, slots, rows)
    compiled = step.lower(
        on(params_specs(cfg)),
        on(jax.ShapeDtypeStruct((slots, 1), jnp.int32)), on(cache),
        bucket).compile()
    return compiled, cache


@pytest.mark.parametrize("arch,slots,bucket", [
    ("granite-3-2b", 64, 128), ("granite-3-2b", 64, 384),
    ("mamba2-1.3b", 24, 384)], ids=["granite-b128", "granite-b384", "mamba2"])
def test_served_decode_updates_cache_in_place(one_chip, arch, slots, bucket):
    # the serving cells' shapes: 384 rows a slot.  The step writes the new
    # rows and states into the donated cache where it lies: no copy of a
    # whole stacked leaf, and no temp buffer of the cache's size
    cfg = get_config(arch, "full")
    compiled, cache = _served_decode(cfg, slots, 384, bucket, one_chip)
    assert not _leaf_copies(compiled.as_text(), cache)
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(cache))
    assert compiled.memory_analysis().temp_size_in_bytes < cache_bytes / 10


@pytest.mark.parametrize("arch", ["granite-3-2b", "mamba2-1.3b", "zamba2-7b"])
def test_served_decode_in_place_on_cpu(arch):
    """The CPU twin of the test above, at the smoke size: no copy of a whole
    stacked cache leaf in the step's entry computation, where a layer loop
    that built a second cache copies it into the donated output.  Inside
    the loop XLA:CPU still copies some leaves that the TPU compiler
    updates in place, so only the TPU test holds the whole program to no
    copies."""
    cfg = get_config(arch, "smoke")
    compiled, cache = _served_decode(cfg, 4, 48, 32)
    hlo = compiled.as_text()
    assert not _leaf_copies(hlo[hlo.index("\nENTRY"):], cache)
