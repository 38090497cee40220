"""chip_smoke.py rehearsed without the chip: its phases called as
functions at ``tiny`` size on the CPU mesh (the dispatch takes the XLA
compositions here, so kernel presence is not asserted — the deviceless
v5e compiles in test_multichip_pallas_compile.py cover that), and the
script itself refusing to run where JAX finds no TPU."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def test_phases_at_tiny_size(smoke, capsys):
    """train -> reference -> serve -> four-device hybrid, every check of
    the real run except the Pallas custom calls."""
    train = smoke.train_phase("tiny", 2, 64, steps=3)
    assert train["losses"][-1] < train["losses"][0]
    assert train["tokens_per_step"] == 64
    ref = smoke.reference_phase("tiny", 2, 64,
                                train_loss0=train["losses"][0])
    assert ref["grad_min_cosine"] >= smoke.GRAD_COSINE
    serve = smoke.serve_phase("tiny", 2, prompt_lens=(5, 11), max_new=6,
                              max_batch=2, max_seq_len=32)
    assert serve["requests"] == 4 and serve["compiles_after_warmup"] == 0
    hybrid = smoke.hybrid_phase("tiny", 2, 64)
    assert hybrid["mesh"] == {"sharding": 2, "mp": 2}
    assert len(hybrid["state_bytes_per_device"]) == 4
    phases = [json.loads(line)["phase"] for line in
              capsys.readouterr().out.splitlines() if line.startswith("{")]
    assert phases == ["train", "reference", "serve", "hybrid",
                      "hybrid_reference", "hybrid_agreement"]


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]],
                         ids=["one-chip", "four-chips"])
def test_script_refuses_a_cpu(argv):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, SCRIPT, *argv], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    assert '"ok"' not in r.stdout and '"phase"' not in r.stdout
