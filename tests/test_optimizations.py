"""§Perf optimization levers keep exact numerics."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS
from repro.data import SyntheticPipeline
from repro.models import attention, build_model
from repro.models.context import ModelContext
from repro.models.params import init_params
from repro.runtime.train import (TrainConfig, cross_entropy,
                                 cross_entropy_chunked, make_loss_fn)


@pytest.mark.parametrize("causal,window,qc", [
    (True, 0, 32), (True, 0, 24), (False, 0, 32), (True, 16, 32),
])
def test_chunked_attention_exact(causal, window, qc):
    r = ARCHS["internlm2-1.8b"].reduced()
    params = init_params(attention.attn_defs(r), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 96, r.d_model))
    pos = jnp.broadcast_to(jnp.arange(96, dtype=jnp.int32), (2, 96))
    o1, kv1 = attention.full_attention(params, x, r, positions=pos,
                                       causal=causal, window=window)
    o2, kv2 = attention.full_attention(params, x, r, positions=pos,
                                       causal=causal, window=window,
                                       attn_impl="chunked", q_chunk=qc)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(kv1[0]), np.asarray(kv2[0]))


def test_chunked_vocab_ce_exact():
    rng = np.random.default_rng(0)
    b, l, d, v = 2, 16, 32, 103      # vocab not divisible by chunk
    hidden = jnp.asarray(rng.normal(size=(b, l, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(d, v)) * 0.1, jnp.float32)
    labels = jnp.asarray(rng.integers(-1, v, size=(b, l)), jnp.int32)
    logits = jnp.einsum("bld,dv->blv", hidden, w)
    full = cross_entropy(logits, labels)
    for chunk in (17, 50, 103, 200):
        ch = cross_entropy_chunked(hidden, w, labels, chunk)
        assert abs(float(full) - float(ch)) < 1e-5, chunk


def test_chunked_vocab_grads_match():
    cfg = ARCHS["gemma3-1b"].reduced()
    model = build_model(cfg)
    params = init_params(model.param_defs(), jax.random.PRNGKey(0))
    pipe = SyntheticPipeline(vocab=cfg.vocab, seq_len=32, global_batch=2)
    batch = pipe.batch(0)
    ctx = ModelContext()
    g1 = jax.grad(lambda p: make_loss_fn(model, ctx, TrainConfig())(
        p, batch)[0])(params)
    g2 = jax.grad(lambda p: make_loss_fn(
        model, ctx, TrainConfig(loss_impl="chunked_vocab", vocab_chunk=128))(
        p, batch)[0])(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-3)


def test_sp_constrain_noop_without_mesh():
    from repro.models.transformer import _sp_constrain
    x = jnp.ones((2, 16, 8))
    ctx = ModelContext(seq_parallel=True)      # no mesh
    assert _sp_constrain(x, ctx) is x


@pytest.mark.slow
def test_seq_parallel_numerics_on_mesh():
    """SP changes sharding, not math: loss identical on a 4-device mesh."""
    import os
    import subprocess
    import sys
    code = """
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
import jax, jax.numpy as jnp
from repro.configs import ARCHS
from repro.data import SyntheticPipeline
from repro.models import build_model
from repro.models.context import ModelContext
from repro.models.params import init_params
from repro.runtime.train import TrainConfig, make_loss_fn
cfg = ARCHS['internlm2-1.8b'].reduced()
model = build_model(cfg)
params = init_params(model.param_defs(), jax.random.PRNGKey(0))
pipe = SyntheticPipeline(vocab=cfg.vocab, seq_len=64, global_batch=4)
batch = pipe.batch(0)
from repro.launch.mesh import make_smoke_mesh
mesh = make_smoke_mesh(1, 4)
with mesh:
    l0 = jax.jit(lambda p, b: make_loss_fn(model, ModelContext(
        mesh=mesh, batch_axes=('data',)), TrainConfig())(p, b)[0])(params, batch)
    l1 = jax.jit(lambda p, b: make_loss_fn(model, ModelContext(
        mesh=mesh, batch_axes=('data',), seq_parallel=True),
        TrainConfig())(p, b)[0])(params, batch)
d = abs(float(l0) - float(l1))
print('DIFF', d)
assert d < 1e-4
print('OK')
"""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, PYTHONPATH=src)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=540, env=env)
    assert "OK" in out.stdout, out.stdout[-1500:] + out.stderr[-2000:]
