"""Validate the §Roofline depth extrapolation against a direct compile.

Costs must be affine in layer count for homogeneous stacks; we check the
(L0=4, L1=8) -> L=12 extrapolation against a directly compiled unrolled
12-layer build of the full-width qwen3 train cell. Runs in a subprocess
with 512 forced host devices (same environment as the dry-run)."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
from repro.launch.roofline import analyze_cell
from repro.launch.dryrun import run_cell

rec = analyze_cell("qwen3_1p7b", "train_4k",
                   cfg_overrides={"n_layers": 12})
assert rec["status"] == "ok", rec
assert rec["depths"] == [4, 8, 12], rec["depths"]

direct = run_cell("qwen3_1p7b", "train_4k", "single", unroll=True,
                  cfg_overrides={"n_layers": 12})
f_direct = direct["cost"]["flops"]
b_direct = direct["cost"]["bytes accessed"]
c_direct = direct["collectives"]["total_bytes"]

def relerr(a, b):
    return abs(a - b) / max(abs(b), 1e-9)

ef = relerr(rec["hlo_flops"], f_direct)
eb = relerr(rec["hlo_bytes"], b_direct)
ec = relerr(rec["collective_bytes"], c_direct)
print(f"flops err {ef:.4f}  bytes err {eb:.4f}  coll err {ec:.4f}")
assert ef < 0.02, ef      # FLOPs are exactly affine in depth
# bytes-accessed drifts slightly with depth (XLA fusion boundaries at
# the unrolled seams differ between builds) — ~10% observed
assert eb < 0.12, eb
assert ec < 0.05, ec
print("OK")
"""


def test_depth_extrapolation_matches_direct_compile():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", CODE], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout, out.stdout
