"""Multi-device tests (subprocess: XLA device-count flag must precede jax
import, and the main test process must keep seeing 1 device)."""
import os
import subprocess
import sys


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8, timeout: int = 560):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", code], env=env, timeout=timeout,
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


def test_sharded_training_loss_decreases():
    out = _run("""
import warnings; warnings.filterwarnings('ignore')
import jax
import repro.configs as C
from repro.launch.mesh import make_mesh
from repro.train.loop import Trainer
mesh = make_mesh((2, 4), ('data', 'model'))
tr = Trainer(C.get_smoke('qwen2-moe-a2.7b'), seq_len=32, global_batch=8,
             total_steps=6, warmup=2, peak_lr=5e-3, mesh=mesh)
o = tr.run(6)
print('LOSSES', o['first_loss'], o['last_loss'])
assert o['last_loss'] == o['last_loss']  # not NaN
p = o['state'].params['blocks']['sub0_moe']['attn']['wq']['w']
print('SPEC', p.sharding.spec)
assert 'model' in str(p.sharding.spec)
""")
    assert "SPEC" in out


def test_elastic_remesh_restore():
    out = _run("""
import warnings, tempfile, os; warnings.filterwarnings('ignore')
import jax
import repro.configs as C
from repro.launch.mesh import make_mesh
from repro.train.loop import Trainer
cfg = C.get_smoke('phi3-mini-3.8b')
with tempfile.TemporaryDirectory() as d:
    m1 = make_mesh((2, 4), ('data', 'model'))
    Trainer(cfg, seq_len=32, global_batch=8, total_steps=4, ckpt_every=2,
            warmup=2, mesh=m1, workdir=d).run(4)
    m2 = make_mesh((4, 2), ('data', 'model'))
    o = Trainer(cfg, seq_len=32, global_batch=8, total_steps=6, ckpt_every=2,
                warmup=2, mesh=m2, workdir=d).run(6)
    assert len(o['losses']) == 2  # resumed from step 4
    print('ELASTIC_OK')
""")
    assert "ELASTIC_OK" in out


def test_fsdp_zero3_training():
    # ZeRO-3 path: params sharded over data+model, bf16 gather pinning
    out = _run("""
import warnings, dataclasses; warnings.filterwarnings('ignore')
import jax
import repro.configs as C
from repro.launch.mesh import make_mesh
from repro.train.loop import Trainer
cfg = C.get_smoke('deepseek-7b')
cfg = cfg.replace(parallel=dataclasses.replace(cfg.parallel,
                                               fsdp_params=True,
                                               grad_accum=2))
mesh = make_mesh((2, 4), ('data', 'model'))
tr = Trainer(cfg, seq_len=32, global_batch=8, total_steps=4, warmup=2,
             peak_lr=5e-3, mesh=mesh)
o = tr.run(4)
p = o['state'].params['blocks']['sub0_attn']['mlp']['wi']['w']
spec = str(p.sharding.spec)
print('FSDP_SPEC', spec)
assert 'data' in spec and 'model' in spec  # 2-D sharded master weights
assert o['losses'][-1] == o['losses'][-1]
""")
    assert "FSDP_SPEC" in out


def test_compressed_allreduce_matches_mean():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as PS
from jax.experimental.shard_map import shard_map
from repro.launch.mesh import make_mesh
from repro.optim.compress import compressed_allreduce
mesh = make_mesh((8,), ('pod',))
x = jnp.asarray(np.random.default_rng(0).normal(0, 1, (8, 64)).astype(np.float32))
f = shard_map(lambda s: compressed_allreduce(s, 'pod'), mesh=mesh,
              in_specs=PS('pod'), out_specs=PS('pod'))
y = f(x)
want = jnp.broadcast_to(x.mean(0, keepdims=True), x.shape)
rel = float(jnp.abs(y - want).max() / jnp.abs(want).max())
print('REL', rel)
assert rel < 0.02
""")
    assert "REL" in out


def test_dryrun_entry_single_cell(tmp_path):
    # the dry-run module itself (512 fake devices) on the cheapest cell
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "smollm-135m",
         "--shape", "decode_32k", "--mesh", "single", "--out",
         str(tmp_path), "--force"],
        env=env, timeout=560, capture_output=True, text=True, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ok lower=" in r.stdout
    assert "FAIL" not in r.stdout, r.stdout
