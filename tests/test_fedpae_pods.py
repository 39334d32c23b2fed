"""Pod-level FedPAE primitives: ring exchange moves the right params and
the on-mesh ensemble vote equals the host-side mean-prob vote. Runs in a
subprocess with 8 fake devices, mesh (pod 2, data 2, model 2)."""
import os
import subprocess
import sys

CODE = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_smoke
from repro.launch.fedpae_pods import pod_ring_exchange, make_ensemble_serve_step
from repro.models import transformer as tf

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = get_smoke("llama3-8b").replace(dtype="float32")
key = jax.random.PRNGKey(0)
members = [tf.init_params(cfg, jax.random.fold_in(key, i)) for i in range(2)]
bench = jax.tree.map(lambda a, b: jnp.stack([a, b]), *members)
shard = jax.tree.map(
    lambda l: NamedSharding(mesh, P(*(["pod"] + [None] * (l.ndim - 1)))), bench)
bench = jax.device_put(bench, shard)

# --- ring exchange: pod 0's params end up in pod 1's slot and vice versa
with mesh:
    swapped = jax.jit(lambda b: pod_ring_exchange(b, mesh),
                      out_shardings=shard)(bench)
for a, b in zip(jax.tree.leaves(bench), jax.tree.leaves(swapped)):
    a, b = np.asarray(a), np.asarray(b)
    np.testing.assert_allclose(a[0], b[1], atol=0)
    np.testing.assert_allclose(a[1], b[0], atol=0)

# --- ensemble serve: psum vote == host mean-prob vote
toks = jax.random.randint(key, (2, 16), 0, cfg.vocab)
chrom = jnp.array([1.0, 1.0], jnp.float32)
step = make_ensemble_serve_step(cfg, mesh)
with mesh:
    vote = jax.jit(step)(bench, chrom, toks)
host = sum(jax.nn.softmax(tf.forward(m, cfg, toks, mode="train",
                                     last_only=True)[0].astype(jnp.float32), -1)
           for m in members) / 2
np.testing.assert_allclose(np.asarray(vote), np.asarray(host), atol=1e-5)

# --- chromosome masks a member out
chrom0 = jnp.array([1.0, 0.0], jnp.float32)
with mesh:
    vote0 = jax.jit(step)(bench, chrom0, toks)
h0 = jax.nn.softmax(tf.forward(members[0], cfg, toks, mode="train",
                               last_only=True)[0].astype(jnp.float32), -1)
np.testing.assert_allclose(np.asarray(vote0), np.asarray(h0), atol=1e-5)
print("OK")
"""


def test_pod_exchange_and_ensemble_vote():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", CODE], env=env,
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
