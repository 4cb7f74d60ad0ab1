"""Training-substrate tests: optimizer, data determinism, microbatching,
gradient compression, pipeline parallelism, training checkpoints."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                       # shim: see _hypothesis_stub
    from _hypothesis_stub import given, settings, strategies as st

from repro import configs
from repro.data.pipeline import synthetic_batch
from repro.models import model as M
from repro.train.checkpoint import restore as t_restore, save as t_save
from repro.train.optim import adamw_init, adamw_update, cosine_lr
from repro.train.step import make_train_step, master_params



pytestmark = pytest.mark.slow      # LM training-substrate tests: full CI on main only
def test_data_pipeline_determinism():
    cfg = configs.smoke("qwen2-7b")
    b1 = synthetic_batch(cfg, 4, 32, seed=7, step=jnp.int32(13))
    b2 = synthetic_batch(cfg, 4, 32, seed=7, step=jnp.int32(13))
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
    b3 = synthetic_batch(cfg, 4, 32, seed=7, step=jnp.int32(14))
    assert not np.array_equal(np.asarray(b1["tokens"]),
                              np.asarray(b3["tokens"]))


def test_labels_are_shifted_tokens():
    cfg = configs.smoke("qwen2-7b")
    b = synthetic_batch(cfg, 2, 16, seed=3, step=jnp.int32(0))
    np.testing.assert_array_equal(np.asarray(b["tokens"])[:, 1:],
                                  np.asarray(b["labels"])[:, :-1])


@given(st.integers(0, 10_000))
@settings(deadline=None, max_examples=20)
def test_cosine_lr_bounds(step):
    lr = float(cosine_lr(jnp.int32(step), peak=1e-3, warmup=100,
                         total=10_000))
    assert 0.0 <= lr <= 1e-3 + 1e-9


def test_adamw_moves_toward_minimum():
    p = {"w": jnp.asarray([5.0, -3.0])}
    opt = adamw_init(p)
    for s in range(200):
        g = {"w": 2 * p["w"]}               # d/dw of w^2
        p, opt = adamw_update(p, g, opt, jnp.int32(s + 1), lr=5e-2,
                              weight_decay=0.0)
    assert float(jnp.abs(p["w"]).max()) < 0.5


def test_microbatching_matches_full_batch():
    """Grad accumulation over M microbatches == one big batch (linearity
    of gradients; losses averaged)."""
    cfg = configs.smoke("qwen2-7b")
    params = master_params(cfg, M.init(cfg, jax.random.PRNGKey(0)))
    opt = adamw_init(params)
    batch = synthetic_batch(cfg, 8, 32, seed=5, step=jnp.int32(0))
    outs = {}
    for nmb in (1, 4):
        step = make_train_step(cfg, mesh=None, microbatches=nmb,
                               block_q=16, block_k=16)
        p2, _, metrics = step(params, opt, batch, jnp.int32(1))
        outs[nmb] = (float(metrics["loss"]),
                     np.asarray(jax.tree_util.tree_leaves(p2)[0],
                                np.float32))
    assert abs(outs[1][0] - outs[4][0]) < 5e-3
    np.testing.assert_allclose(outs[1][1], outs[4][1], rtol=2e-2,
                               atol=2e-3)


def test_train_checkpoint_roundtrip(tmp_path):
    cfg = configs.smoke("mamba2-130m")
    params = master_params(cfg, M.init(cfg, jax.random.PRNGKey(0)))
    opt = adamw_init(params)
    path = str(tmp_path / "t.ckpt")
    t_save(path, params, opt, step=17)
    p2, o2, step = t_restore(path, params, opt)
    assert step == 17
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


_MULTIDEV = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P

# --- gradient compression: int8 psum with error feedback ---------------
from repro.train.compression import compressed_psum
mesh = jax.make_mesh((8,), ("data",))

def body(g):
    out, err = compressed_psum({"g": g}, ("data",))
    return out["g"], err["g"]

fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("data"),),
                           out_specs=(P("data"), P("data")),
                           check_vma=False))
g = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
mean, err = fn(g)
true_mean = jnp.mean(g, axis=0)
# int8 quantization: per-worker error <= scale/2; mean error small.
scale = float(jnp.max(jnp.abs(g))) / 127.0
got = np.asarray(mean)
assert np.max(np.abs(got - np.asarray(true_mean)[None, :])) <= scale, (
    np.max(np.abs(got - np.asarray(true_mean)[None, :])), scale)
# error feedback residual = g - q*scale (bounded by scale/2 per element)
assert float(jnp.max(jnp.abs(err))) <= scale * 0.51 + 1e-9
print("COMPRESSION_OK")

# --- pipeline parallelism: 4 stages x identity-ish stages --------------
from repro.distributed.pipeline_parallel import pipeline_forward
mesh2 = jax.make_mesh((4,), ("stage",))
S, M_, mb, d = 4, 6, 2, 16
ws = jax.random.normal(jax.random.PRNGKey(1), (S, d, d)) * 0.1 \
    + jnp.eye(d)[None]

def stage_fn(w, x):
    return jnp.tanh(x @ w)

x = jax.random.normal(jax.random.PRNGKey(2), (M_, mb, d))
out = pipeline_forward(stage_fn, ws, x, mesh2)
# reference: sequential application of the 4 stages
ref = x
for s in range(S):
    ref = jnp.tanh(ref @ ws[s])
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                           atol=2e-5)
print("PIPELINE_OK")
"""


@pytest.mark.parametrize("marker", ["COMPRESSION_OK", "PIPELINE_OK"])
def test_multidevice_substrate(marker, multidev_output):
    assert marker in multidev_output


@pytest.fixture(scope="module")
def multidev_output():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _MULTIDEV], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc.stdout
