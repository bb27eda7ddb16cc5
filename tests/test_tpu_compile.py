"""The Pallas kernels of the sweep's main path compile for a TPU v5e.

Each test compiles one kernel at real width for a described (not attached)
v5e:2x2 topology with the TPU compiler installed next to JAX, and checks
that the Mosaic kernel is in the compiled program (`tpu_custom_call`).
Interpret-mode tests cannot see what only the chip's compiler refuses:
block shapes Mosaic does not tile, or more VMEM than a kernel may use.
Nothing runs, so these say nothing about results or speed.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and under pytest-xdist every worker imports every test file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import defense_sort as DS
from repro.kernels import floa_aggregate as FA
from repro.kernels import grad_stats as GS

S, U, D = 16, 10, 1 << 20


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compilation
    cache off (a compile for a described chip cannot be read back)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_floa_step_batched_compiles(one_chip):
    text = _compile(FA.floa_step_batched, one_chip,
                    (S, D), (S, U), (S, U, D), (S, D), (S,), (S,), (S,))
    assert "tpu_custom_call" in text


def _d_wide(text, d, ops):
    """Instructions of the compiled text whose name starts with one of
    `ops` and whose result has a dimension of d or more."""
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%((?:" + "|".join(ops) + r")[\w.-]*) = "
                     r"(.*?)(?:, metadata=|$)", line)
        if m and any(int(n) >= d for shape in re.findall(r"\[([\d,]+)\]",
                                                         m.group(2))
                     for n in shape.split(",")):
            found.append(m.group(1))
    return found


def test_floa_step_batched_compiles_at_qwen_width(one_chip):
    """At the qwen3-4b-ota cell's shapes (D = 150,085,376, not a multiple
    of any power-of-two tile above 256) the kernel takes its operands as
    they are: no pad of D and no relayout copy of a D-wide operand or
    result around the custom call."""
    s, u, d = 2, 4, 150_085_376
    text = _compile(FA.floa_step_batched, one_chip,
                    (s, d), (s, u), (s, u, d), (s, d), (s,), (s,), (s,))
    assert "tpu_custom_call" in text
    assert _d_wide(text, d, ("pad", "copy")) == []


def test_floa_aggregate_batched_compiles(one_chip):
    text = _compile(FA.floa_aggregate_batched, one_chip,
                    (S, U), (S, U, D), (S, D), (S,), (S,))
    assert "tpu_custom_call" in text


def test_floa_aggregate_compiles(one_chip):
    text = _compile(FA.floa_aggregate, one_chip, (U,), (U, D), (D,), (), ())
    assert "tpu_custom_call" in text


def test_grad_stats_compiles(one_chip):
    assert "tpu_custom_call" in _compile(GS.grad_stats, one_chip, (U, D))


def test_sort_columns_vmapped_compiles(one_chip):
    text = _compile(jax.vmap(DS.sort_columns), one_chip, (S, U, D))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("u,d", [(1000, 1 << 16),   # U_pad 1024
                                 (8192, 1 << 14)])  # U_pad = BITONIC_MAX_U
def test_sort_columns_bitonic_compiles(one_chip, u, d):
    """The bitonic kernel's VMEM (tile width and scoped limit) fits v5e
    from a thousand workers up to BITONIC_MAX_U."""
    text = _compile(DS.sort_columns_bitonic, one_chip, (u, d))
    assert "tpu_custom_call" in text
