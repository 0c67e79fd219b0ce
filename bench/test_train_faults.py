"""The training cell at a test's size on four host devices (each case in
a subprocess, which takes the device count at start): a sound run and
its control, and runs with the program broken underneath, each of
which must come out not correct.

Faults: AdamW's moments reset at a resize (the checksums differ);
cross-attention dropped from every layer; one codebook's loss left out
of the mean; a resize that keeps the old mesh; the big mesh's step
training on half the batch."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent

# The small cell: musicgen's reduced architecture (two layers, width 64,
# four heads of 16, ffn 128, four codebooks of 256 codes, text width 32)
# in bfloat16, batch 8 of 32 frames with text of 8-16 tokens, a 3-s
# window, and limits set from its own readings on the CPU (PERF.md,
# section 2).
RUNNER = r"""
import dataclasses, json, sys, time
sys.path.insert(0, {bench!r}); sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp
import run
from harness import train
from harness.cell import load_cell
from repro.configs.registry import get_config

arch = dataclasses.replace(get_config("musicgen-medium").reduced(),
                           dtype="bfloat16")
train.program_arch = lambda c: arch
cell = load_cell("musicgen-16l.elastic4")
cell.config = dict(cell.config, hidden_size=64, num_hidden_layers=2,
                   num_attention_heads=4, head_dim=16, ffn_dim=128,
                   vocab_size=256, text_encoder_hidden_size=32)
cell.config["optimizer"] = dict(cell.config["optimizer"], warmup_steps=4)
cell.traffic = dict(cell.traffic, batch=8, frames=32, text_len_max=16)
cell.limits = {limits}

fault = {fault!r}
from repro.models import transformer
from repro.runtime.elastic import ElasticRuntime
if fault == "moments_reset":
    bind = ElasticRuntime.bind
    def reset(self, key=None):
        moving = self.params is not None
        bind(self, key)
        if moving:
            self.opt_state = self.opt_state._replace(mu=jax.tree_util.tree_map(
                jnp.zeros_like, self.opt_state.mu))
    ElasticRuntime.bind = reset
elif fault == "xattn_dropped":
    transformer.cross_attention = lambda x, *a, **k: jnp.zeros_like(x)
elif fault == "codebook_left_out":
    ce = transformer.codebook_cross_entropy
    transformer.codebook_cross_entropy = \
        lambda logits, labels, special: ce(logits[:, 1:], labels[:, 1:], special)
elif fault == "old_mesh":
    usable = ElasticRuntime._usable_devices
    ElasticRuntime._usable_devices = \
        lambda self: self.mesh.size if self.mesh is not None else usable(self)
elif fault == "half_batch_big_mesh":
    step = ElasticRuntime.step
    def half(self, batch):
        if self.mesh.size == 4:
            labels = batch["labels"].copy()
            labels[labels.shape[0] // 2:] = cell.config["vocab_size"]
            batch = dict(batch, labels=labels)
        return step(self, batch)
    ElasticRuntime.step = half
extra = {{}}
res = run.run_cell(cell.workload["name"], {seed}, 3.0, False,
                   require_chip=False, t_process=time.perf_counter(),
                   cell=cell, driver_kwargs={kwargs},
                   on_extra=extra.update)
print(json.dumps({{"res": res, "control": extra.get("control_gap")}}))
"""

LIMITS = {"reshard_mismatch": 0, "resize_faults": 0, "grad_gap": 0.025,
          "update_gap": 0.01}


def run_small(fault=None, seed=2718281828, control=False):
    code = RUNNER.format(bench=str(BENCH), src=str(BENCH.parent / "src"),
                         limits=LIMITS, fault=fault, seed=seed,
                         kwargs={"control": True} if control else {})
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_train_sound_and_control_fails():
    got = run_small(control=True)
    res, ctl = got["res"], got["control"]
    assert res["correct"], res["checks"]
    assert res["metrics"]["tokens_per_s"]["value"] > 0
    assert any(ctl[k] > LIMITS[k] for k in ctl if k in LIMITS), ctl


@pytest.mark.parametrize("fault,check", [
    ("moments_reset", "reshard_mismatch"), ("xattn_dropped", "grad_gap"),
    ("codebook_left_out", "grad_gap"), ("old_mesh", "resize_faults"),
    ("half_batch_big_mesh", "grad_gap.4")])
def test_train_fault(fault, check):
    res = run_small(fault)["res"]
    assert not res["correct"], res["checks"]
    c = res["checks"][check]
    assert c["value"] > c["limit"], res["checks"]
