"""Helpers for the benchmark's own tests: cells of the real benchmark,
cut to sizes a CPU test run can hold.  Run from the checkout root:

    JAX_PLATFORMS=cpu python -m pytest -q bench
"""
import copy
import dataclasses
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


@pytest.fixture
def small_sched():
    """``quartz.*`` at 64 nodes, depth 32."""
    from harness.cell import load_cell

    def make(workload):
        cell = load_cell(workload)
        cell.config = copy.deepcopy(cell.config)
        cell.config["site"]["nodes"] = 64
        cell.traffic = dict(cell.traffic, warmup_rounds=5)
        if "depth" in cell.traffic:
            cell.traffic["depth"] = 32
        return cell
    return make


@pytest.fixture
def small_decode(monkeypatch):
    """``phi4-mini.decode`` with the program's reduced phi4-mini (two
    layers, width 64, vocabulary 256, tied embeddings) in bfloat16,
    batch 8, prompt 16, 32 tokens, 8 requests checked, and a limit set
    from its own readings on CPU: over 16 runs (14 seeds) the program's
    widest gap read at most 0.0336 and the control's at least 0.147."""
    from harness import decode
    from harness.cell import load_cell
    from repro.configs.registry import get_config
    from repro.models.model import make_model
    arch = dataclasses.replace(get_config("phi4-mini-3.8b").reduced(),
                               dtype="bfloat16", tie_embeddings=True)
    monkeypatch.setattr(decode, "program_model", lambda c: make_model(arch))
    cell = load_cell("phi4-mini.decode")
    cell.config = dict(cell.config, hidden_size=64, num_attention_heads=4,
                       num_key_value_heads=2, head_dim=16,
                       intermediate_size=128, vocab_size=256,
                       num_hidden_layers=2)
    cell.traffic = dict(cell.traffic, batch=8, prompt_len=16, gen_len=32,
                        check_requests=8)
    cell.limits = {"served_logit_gap": 0.08}
    return cell


def run_small(cell, seed=1234567, seconds=0.5, **driver_kwargs):
    import time

    import run
    extra = {}
    res = run.run_cell(cell.workload["name"], seed, seconds, False,
                       require_chip=False, t_process=time.perf_counter(),
                       cell=cell, driver_kwargs=driver_kwargs,
                       on_extra=extra.update)
    return res, extra
