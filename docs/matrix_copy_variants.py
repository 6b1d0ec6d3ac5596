"""Why the chunk program copied the whole packed matrix ~700 times a tree, as a
skeleton (PR 27; PERF.md section 5 has the table this prints).

    JAX_PLATFORMS=cpu python docs/matrix_copy_variants.py

compiles seven control-flow variants for a described TPU v5e (nothing runs;
a few seconds in all in the sandbox) and counts the ``copy`` instructions of the
carry's shape in the compiled text.  The carry stands for the packed matrix,
``touch`` for split_stream / level_stream: a Pallas kernel that writes its
operand in place (``input_output_aliases``).  The rule the counts give, and
ops/pgrow.py and boosting/ptrainer.py now follow: the matrix goes loop carry ->
aliased kernel -> loop carry and through NO ``lax.cond``.  A conditional that
carries it costs a copy in the branch that returns it untouched, and two in
every loop body nested inside ANY conditional that carries it."""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl, topologies
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import SingleDeviceSharding

SHAPE = (16, 1 << 20)


def touch(p, i):
    """In-place kernel: adds 1 to the 128 lanes at block ``i``."""
    def kernel(i_ref, p_in, p_out, buf, sem):
        at = p_out.at[:, pl.ds(pl.multiple_of(i_ref[0] * 128, 128), 128)]
        cp = pltpu.make_async_copy(at, buf, sem)
        cp.start(), cp.wait()
        buf[...] += 1
        cp = pltpu.make_async_copy(buf, at, sem)
        cp.start(), cp.wait()

    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct(p.shape, p.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((16, 128), jnp.int32), pltpu.SemaphoreType.DMA(())]),
        input_output_aliases={1: 0}, name="touch")(jnp.reshape(i, (1,)), p)


def program(outer_cond, replay):
    """``replay``: 'direct' (kernel called by the loop body), 'conds' (cond(gain > 0)
    -> cond(has_pre) with a branch that returns p untouched: the parent),
    'one_cond' (gain > 0 folded into the predicate) or 'empty_segment' (kernel
    unconditional on a segment zeroed when pre; cond over the small table only)."""
    def split(s):
        p, k, tab = s
        pre = tab[k % 8] > 0
        if replay == "empty_segment":
            p = touch(p, jnp.where(pre, 0, k))
            tab = jax.lax.cond(pre, lambda t: t + 1, lambda t: t * 2, tab)
        elif replay == "direct":
            p = touch(p, k)
        else:
            p, tab = jax.lax.cond(pre, lambda p, t: (p, t + 1),
                                  lambda p, t: (touch(p, k), t * 2), p, tab)
        return p, k + 1, tab

    def one_iter(c):
        p, tab, stopped = c
        p, _ = jax.lax.while_loop(  # the level loop: no conditional of its own
            lambda s: s[1] < 9, lambda s: (touch(s[0], s[1]), s[1] + 1), (p, jnp.int32(0)))
        if replay == "conds":
            body = lambda s: jax.lax.cond(s[2][0] > 0, split, lambda s: (s[0], jnp.int32(254), s[2]), s)
            pred = lambda s: s[1] < 254
        else:
            body, pred = split, lambda s: (s[1] < 254) & (s[2][0] > 0)
        p, _, tab = jax.lax.while_loop(pred, body, (p, jnp.int32(0), tab))
        return p, tab, tab[1] > 99

    def prog(p, tab, t_run):
        if outer_cond:  # the parent: every iteration inside lax.cond(stopped, no-op, live)
            body = lambda t, c: jax.lax.cond(c[2], lambda c: c, one_iter, c)
            return jax.lax.fori_loop(0, t_run, body, (p, tab, jnp.array(False)))[:2]
        t, c = jax.lax.while_loop(lambda tc: (tc[0] < t_run) & ~tc[1][2],
                                  lambda tc: (tc[0] + 1, one_iter(tc[1])),
                                  (jnp.int32(0), (p, tab, jnp.array(False))))
        return c[:2]

    return jax.jit(prog, donate_argnums=(0,))


VARIANTS = [
    ("A. no outer conditional; inner loops call the kernel directly", False, "direct"),
    ("B. A + the outer lax.cond(stopped, lambda c: c, one_iter, c)", True, "direct"),
    ("C. B + cond(gain > 0) -> cond(has_pre) returning p untouched (the parent)", True, "conds"),
    ("D. C without the outer conditional", False, "conds"),
    ("E. D with gain > 0 folded into the while predicate", False, "one_cond"),
    ("F. no outer conditional; kernel unconditional on an empty segment", False, "empty_segment"),
    ("G. F inside the outer conditional (ROADMAP S1's old 'replay only')", True, "empty_segment"),
]

if __name__ == "__main__":
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    spec = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)
    for label, outer_cond, replay in VARIANTS:
        text = program(outer_cond, replay).lower(spec(SHAPE), spec((8,)), spec(())).compile().as_text()
        sites = re.findall(r" = s32\[16,1048576\]\S* copy\(", text)
        print(f"{len(sites)} whole-matrix copy sites, {len(re.findall(r' conditional[(]', text))} "
              f"conditionals: {label}")
