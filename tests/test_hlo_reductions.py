"""Unit tests for the HLO reduction counters (utils/hlo.py) that the
communication-avoiding solver pins stand on: computation splitting,
while-body discovery (transitive through fusions/nested whiles),
sync/async all-reduce counting with scope="body"/"all", and
``assert_single_reduction`` — both on synthetic HLO text (exact,
compiler-independent) and on a live jitted program.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax import lax

from pylops_mpi_tpu.utils import hlo


# ------------------------------------------------ synthetic HLO text
# A hand-written module shaped like XLA's text dump: an entry with a
# while, whose body calls a fusion that performs one all-reduce, plus
# a setup all-reduce outside the loop and an async pair in the body.
_SYNTH = """\
HloModule synth, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%add.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(f32[] %a, f32[] %b)
}

%fused_dot (p: f32[8]) -> f32[] {
  %p = f32[8] parameter(0)
  %ar.2 = f32[8] all-reduce(f32[8] %p), to_apply=%add.1
  ROOT %s = f32[] constant(0)
}

%body.3 (carry: (f32[8], s32[])) -> (f32[8], s32[]) {
  %carry = (f32[8], s32[]) parameter(0)
  %v = f32[8] get-tuple-element((f32[8], s32[]) %carry), index=0
  %i = s32[] get-tuple-element((f32[8], s32[]) %carry), index=1
  %g = f32[] fusion(f32[8] %v), kind=kLoop, calls=%fused_dot
  %st = f32[8] all-reduce-start(f32[8] %v), to_apply=%add.1
  %dn = f32[8] all-reduce-done(f32[8] %st)
  ROOT %t = (f32[8], s32[]) tuple(f32[8] %dn, s32[] %i)
}

%cond.4 (carry: (f32[8], s32[])) -> pred[] {
  %carry = (f32[8], s32[]) parameter(0)
  ROOT %p = pred[] constant(true)
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8] parameter(0)
  %setup = f32[8] all-reduce(f32[8] %x), to_apply=%add.1
  %w = (f32[8], s32[]) tuple(f32[8] %setup, s32[] constant(0))
  %loop = (f32[8], s32[]) while((f32[8], s32[]) %w), condition=%cond.4, body=%body.3
  ROOT %out = f32[8] get-tuple-element((f32[8], s32[]) %loop), index=0
}
"""


def test_computations_split():
    comps = hlo._computations(_SYNTH)
    for name in ("add.1", "fused_dot", "body.3", "cond.4", "main"):
        assert name in comps, sorted(comps)
    assert any("all-reduce-start" in ln for ln in comps["body.3"])
    assert not any("while(" in ln for ln in comps["fused_dot"])


def test_while_body_transitive_closure():
    bodies = hlo.while_body_computations(_SYNTH)
    # the body itself, the fusion it calls, and the to_apply reducer —
    # but NEVER the entry or the condition
    assert "body.3" in bodies
    assert "fused_dot" in bodies
    assert "add.1" in bodies
    assert "main" not in bodies
    assert "cond.4" not in bodies


def test_count_reductions_scopes():
    # body: the fused all-reduce + the async start (done halves are
    # never counted); all: those two plus the setup reduce
    assert hlo.count_reductions(_SYNTH, scope="body") == 2
    assert hlo.count_reductions(_SYNTH, scope="all") == 3
    with pytest.raises(ValueError, match="scope"):
        hlo.count_reductions(_SYNTH, scope="entry")


def test_count_reductions_ignores_operand_mentions():
    # an instruction CONSUMING an all-reduce's result (%ar.2 as an
    # operand) is not itself a reduction
    text = ("ENTRY %m (x: f32[4]) -> f32[4] {\n"
            "  %x = f32[4] parameter(0)\n"
            "  %ar.2 = f32[4] all-reduce(f32[4] %x), to_apply=%add\n"
            "  ROOT %c = f32[4] copy(f32[4] %ar.2)\n"
            "}\n")
    assert hlo.count_reductions(text, scope="all") == 1
    # no while loop at all -> body scope counts nothing
    assert hlo.count_reductions(text, scope="body") == 0


# ------------------------------------------------ live jitted program
def _psum_loop(x):
    """One psum per iteration inside a while loop, plus one setup
    psum outside it — the exact shape the CA pins must separate."""
    seed = jax.lax.psum(x, "d")

    def body(i, c):
        return c + jax.lax.psum(c * 0.5, "d")

    return lax.fori_loop(0, 4, body, seed)


def _shmapped():
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    devs = np.array(jax.devices())
    mesh = Mesh(devs, ("d",))
    return shard_map(_psum_loop, mesh=mesh, in_specs=P("d"),
                     out_specs=P("d"), check_vma=False)


def test_live_body_vs_all_scope():
    n = len(jax.devices()) * 4
    x = jnp.arange(n, dtype=jnp.float32)
    f = _shmapped()
    text = hlo.compiled_hlo(f, x)
    n_body = hlo.count_reductions(text, scope="body")
    n_all = hlo.count_reductions(text, scope="all")
    assert n_body == 1
    assert n_all >= 2  # setup reduction outside the loop is extra


def test_assert_single_reduction_live():
    n = len(jax.devices()) * 4
    x = jnp.arange(n, dtype=jnp.float32)
    hlo.assert_single_reduction(_shmapped(), x)


def test_assert_single_reduction_raises_with_context():
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    def two_per_iter(x):
        def body(i, c):
            # DEPENDENT reductions: the second needs the first's
            # result, so they are two latency floors on the critical
            # path. Two independent psums no longer count as two —
            # this XLA's all-reduce combiner folds them into one
            # variadic all-reduce by itself.
            a = jax.lax.psum(c, "d")
            b = jax.lax.psum(c * a, "d")
            return c + a * 0.1 + b * 0.01

        return lax.fori_loop(0, 4, body, x)

    mesh = Mesh(np.array(jax.devices()), ("d",))
    f = shard_map(two_per_iter, mesh=mesh, in_specs=P("d"),
                  out_specs=P("d"), check_vma=False)
    n = len(jax.devices()) * 4
    x = jnp.arange(n, dtype=jnp.float32)
    with pytest.raises(AssertionError, match="all-reduce"):
        hlo.assert_single_reduction(f, x)
