"""A run with the timed path broken underneath comes out not correct:
the harness's look for a chip is skipped, the rest of a run is driven
as the benchmark drives it, on cells cut to a test's size.

Faults the cells can have:
* scheduling: an answer altered where it is produced (the policy's
  backfill starts nothing; the matcher hands back one core short);
* serving: a token altered where it is produced, a step that returns
  its state (the KV cache) unchanged, half of the batch left out."""
import jax
import jax.numpy as jnp
import pytest
from conftest import run_small


def test_sched_backfill_answer_altered(small_sched, monkeypatch):
    from repro.core import policy
    monkeypatch.setattr(policy.EasyBackfill, "backfill",
                        lambda self, queue, head: 0)
    res, _ = run_small(small_sched("quartz.backlog"))
    assert not res["correct"]
    assert res["checks"]["start_mismatch"]["value"] > 0


def test_sched_match_answer_altered(small_sched, monkeypatch):
    from repro.core import match
    orig = match.Matcher.match

    def short(self, jobspec):
        got = orig(self, jobspec)
        return None if got is None else got[:-1]
    monkeypatch.setattr(match.Matcher, "match", short)
    res, _ = run_small(small_sched("quartz.open"))
    assert not res["correct"]
    assert res["checks"]["alloc_faults"]["value"] > 0


def _token_altered(prefill, serve):
    def bad(p, cache, batch, pos):
        logits, cache = serve(p, cache, batch, pos)
        return logits.at[:, :, 0].add(1e4), cache
    return prefill, bad


def _state_unchanged(prefill, serve):
    def bad(p, cache, batch, pos):
        keep = jax.tree_util.tree_map(jnp.copy, cache)
        logits, _ = serve(p, cache, batch, pos)
        return logits, keep
    return prefill, bad


def _half_batch(prefill, serve):
    def bad(p, cache, batch, pos):
        logits, cache = serve(p, cache, batch, pos)
        h = logits.shape[0] // 2
        return jnp.concatenate([logits[:h], logits[:h]]), cache
    return prefill, bad


def test_decode_sound(small_decode):
    res, _ = run_small(small_decode)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged,
                                   _half_batch])
def test_decode_fault(small_decode, fault):
    res, _ = run_small(small_decode, fault=fault)
    assert not res["correct"], res["checks"]


def test_no_chip_no_result(capsys):
    import run
    with pytest.raises(SystemExit) as e:
        run.run_cell("quartz.backlog", 1, 1.0, False)
    assert e.value.code == run.NO_CHIP
    assert "{" not in capsys.readouterr().out
