"""Elastic restart: checkpoint on one mesh, resume on a DIFFERENT mesh.

Phase 1 trains a reduced model data-parallel on 4 (forced host) devices
and checkpoints. Phase 2 — a separate process standing in for the
rescheduled job — restores the same checkpoint onto a 2-device mesh
(half the "pod" survived) and keeps training. The checkpoint stores only
logical metadata, so restore re-device_puts each leaf with the target
mesh's shardings.

Both phases are meant for forced host (CPU) devices
(``--xla_force_host_platform_device_count``): the example shows a restart
onto a smaller mesh, which needs more devices than one chip has. It is not
a chip workload; on a machine with a chip, run it with
``JAX_PLATFORMS=cpu``.

Run:  PYTHONPATH=src python examples/elastic_restart.py
"""
import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PHASE = r"""
import os, sys
import numpy as np
import jax, jax.numpy as jnp
from repro.configs.base import get_config, reduced_config
from repro.data.pipeline import KGTokenPipeline
from repro.distributed.checkpoint import CheckpointManager
from repro.distributed.sharding import init_params, param_shardings
from repro.launch.mesh import make_mesh
from repro.models import auto_rules, get_model
from repro.models.layers import ShardCtx
from repro.train.optimizer import make_optimizer
from repro.train.train_step import make_train_step

ckpt, n_dev, start, stop = (sys.argv[1], int(sys.argv[2]), int(sys.argv[3]),
                            int(sys.argv[4]))
cfg = reduced_config(get_config("qwen3-1.7b"))
mesh = make_mesh((n_dev,), ("data",))
rules = auto_rules(cfg, mesh)
model = get_model(cfg.family)
opt = make_optimizer(cfg.optimizer, lr=1e-2)
step_fn = jax.jit(make_train_step(cfg, optimizer=opt,
                                  ctx=ShardCtx(mesh, rules)))
specs = model.param_specs(cfg)
shardings = param_shardings(specs, mesh, rules)
params = jax.device_put(init_params(specs, jax.random.PRNGKey(0)), shardings)
opt_state = opt.init(params)
manager = CheckpointManager(ckpt, keep_n=2, async_write=False)
if manager.latest_step() is not None:
    (params, opt_state), extra = manager.restore((params, opt_state))
    # elastic: re-place parameters with THIS mesh's shardings
    params = jax.device_put(params, shardings)
    print(f"[{n_dev}dev] restored step {extra['step']}", flush=True)

stream = (np.arange(20000) % 250 + 4).astype(np.int32)
pipe = KGTokenPipeline(stream, seq_len=32, global_batch=8)
for s in range(start, stop):
    batch = {k: jnp.asarray(v) for k, v in pipe.batch(s).items()}
    params, opt_state, m = step_fn(params, opt_state, batch,
                                   jnp.asarray(s, jnp.int32))
    print(f"[{n_dev}dev] step {s} loss {float(m['loss']):.4f}", flush=True)
manager.save(stop - 1, (params, opt_state), extra={"step": stop - 1})
manager.close()
"""


def run_phase(ckpt: str, n_dev: int, start: int, stop: int) -> None:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", PHASE, ckpt, str(n_dev), str(start),
         str(stop)], env=env, capture_output=True, text=True, timeout=900)
    sys.stdout.write(out.stdout)
    if out.returncode:
        sys.stderr.write(out.stderr)
        raise SystemExit(f"phase on {n_dev} devices failed")


if __name__ == "__main__":
    ckpt = tempfile.mkdtemp(prefix="elastic_ckpt_")
    print("phase 1: 4-device data-parallel mesh")
    run_phase(ckpt, n_dev=4, start=0, stop=6)
    print("phase 2: resume the SAME checkpoint on a 2-device mesh")
    run_phase(ckpt, n_dev=2, start=6, stop=12)
    print("elastic restart OK:", ckpt)
