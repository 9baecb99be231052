"""Launch-layer tests: cell building, EF lowering, VMEM tile budgets."""
import os
import subprocess
import sys

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_opt_state_specs_match_init_structure():
    import jax
    from repro.configs.base import get_config, reduced_config
    from repro.distributed.sharding import init_params
    from repro.launch.specs import opt_state_specs
    from repro.models import get_model
    from repro.train.optimizer import make_optimizer
    for arch, opt_name in (("qwen3-1.7b", "adamw"),
                           ("mistral-large-123b", "adafactor")):
        cfg = reduced_config(get_config(arch))
        model = get_model(cfg.family)
        p_specs = model.param_specs(cfg)
        params = init_params(p_specs, jax.random.PRNGKey(0))
        opt = make_optimizer(opt_name)
        real = opt.init(params)
        spec = opt_state_specs(opt_name, p_specs)
        s_real = jax.tree_util.tree_structure(real)
        s_spec = jax.tree_util.tree_structure(
            jax.tree_util.tree_map(lambda s: 0, spec,
                                   is_leaf=lambda x: hasattr(x, "shape")
                                   and not isinstance(x, dict)))
        assert s_real == s_spec, (arch, opt_name)


def test_param_counts_active_vs_total():
    from repro.configs.base import get_config
    from repro.launch.specs import model_param_counts
    k = model_param_counts(get_config("kimi_k2_1t_a32b"))
    assert k["active"] < k["total"] * 0.05     # 384e top-8 => ~2% + dense
    d = model_param_counts(get_config("qwen3_1p7b"))
    assert d["active"] == d["total"]           # dense: all params active


def test_ef_pod_decoupled_cell_lowers():
    """grad_compress_pods=True on a non-FSDP arch: the pod-decoupled
    shard_map train step lowers + compiles on the multi-pod mesh, and the
    cross-pod classifier finds the quantized psum."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
from repro.launch.dryrun import run_cell
from repro.launch.hlo_analysis import collective_bytes
rec = run_cell('qwen3_1p7b', 'train_4k', 'multi', unroll=False,
               cfg_overrides={"grad_compress_pods": True}, keep_hlo=True)
assert rec["status"] == "ok"
st = collective_bytes(rec["hlo_text"], pod_boundary=256)
assert st.cross_pod_bytes > 0
print("OK", st.cross_pod_bytes)
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2500:]
    assert "OK" in out.stdout


def test_kernel_tiles_fit_vmem():
    """Analytic VMEM budgets for the default BlockSpec tiles at production
    dims (v5e: ~16 MiB VMEM/core; keep tiles under half for double
    buffering)."""
    VMEM = 16 * 2**20
    budget = VMEM // 2

    # flash attention: q/k/v/acc tiles at block 128, d_head<=256, f32 acc
    bq = bk = 128
    for d in (64, 128, 256):
        tile = (bq * d + 2 * bk * d) * 2 + bq * d * 4 + 3 * bq * 4
        assert tile < budget, ("flash", d, tile)

    # rwkv6: per-chunk r/k/v/w [chunk, N] + state [N, N] f32, chunk 32
    for n in (64, 128):
        tile = 4 * 32 * n * 4 + n * n * 4 + 32 * 32 * 4
        assert tile < budget, ("rwkv6", n, tile)

    # mamba2 SSD: chunk 64, headdim<=128, state<=128
    for p, n in ((64, 64), (128, 128)):
        tile = 64 * p * 4 + 2 * 64 * n * 4 + n * p * 4 + 64 * 64 * 4
        assert tile < budget, ("mamba2", p, n, tile)

    # rowhash / hash_neighbor_flags: K lane-dense column planes of the
    # default 8192-row block (K <= 16), the previous block's last (8, 128)
    # group per column, and three [block_n] 32-bit outputs
    from repro.kernels.rowhash.rowhash import DEFAULT_BLOCK_N, ROW_TILE
    tile = (DEFAULT_BLOCK_N * 16 + ROW_TILE * 16 + 3 * DEFAULT_BLOCK_N) * 4
    assert tile < budget, ("rowhash", tile)
