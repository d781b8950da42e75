"""Examples must actually run: each script is executed end-to-end in a
subprocess on the CPU mesh (they self-bootstrap via examples/_cpu_mesh).
The examples are the migrating user's first contact; a broken import or
API drift there must fail CI, not ship silently."""

import os
import subprocess
import sys

import pytest

EXAMPLES_DIR = os.path.join(os.path.dirname(__file__), "..", "examples")

SCRIPTS = [
    "train_llama_hybrid.py",
    "migrate_from_paddle.py",
    "finetune_bert_classifier.py",
    "generate_text.py",
    "audio_keyword_spotting.py",
]


@pytest.mark.parametrize("script", SCRIPTS)
def test_example_runs(script):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, script],
        cwd=EXAMPLES_DIR, env=env,
        capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, (
        f"{script} failed:\nSTDOUT:\n{r.stdout[-1500:]}\n"
        f"STDERR:\n{r.stderr[-1500:]}")
    assert r.stdout.strip(), f"{script} printed nothing"
