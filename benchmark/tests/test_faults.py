"""The rest of a run with the timed path broken underneath: the
harness's look for a chip is skipped (--rehearsal), everything else
runs, and ``correct`` has to come out false; once for each fault a
one-chip cell can have. (The exchange between chips left out belongs
to the four-chip cell's PR.) A sound run of the same sizes comes out
true in test_rehearsal.py."""

import json
import os

import pytest

from benchmark import run as bench_run

TESTS = os.path.join("benchmark", "tests")
TRAIN, SERVE = "train.mistral7b-L2.seq4k", "serve.mistral7b-L16.chat-steady"


def run_cell(capsys, workload, tiny):
    rc = bench_run.main(["--workload", workload, "--seed", "4242",
                         "--seconds", "2", "--trace", "0", "--rehearsal",
                         os.path.join(TESTS, tiny)])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def failing(line):
    return sorted(n for n, c in line["checks"].items()
                  if not c["value"] <= c["limit"])


def break_train_step(monkeypatch, wrap):
    from ray_tpu.models import train_step as ts

    real = ts.make_train_step

    def broken(model, optimizer, **kw):
        return wrap(real(model, optimizer, **kw))

    monkeypatch.setattr(ts, "make_train_step", broken)


def test_step_that_returns_its_state_unchanged(monkeypatch, capsys):
    def wrap(step):
        def unchanged(params, opt_state, batch):
            _, _, metrics = step(params, opt_state, batch)
            return params, opt_state, metrics
        return unchanged

    break_train_step(monkeypatch, wrap)
    line = run_cell(capsys, TRAIN, "tiny_train.json")
    assert line["correct"] is False
    bad = failing(line)
    assert "grad_norm_gap_worst_leaf" in bad
    assert "param_change_gap_worst_leaf" in bad
    # no first moment was kept and no parameter moved: both read 1
    assert line["checks"]["grad_norm_gap_worst_leaf"]["value"] == \
        pytest.approx(1.0)
    assert line["checks"]["param_change_gap_worst_leaf"]["value"] == \
        pytest.approx(1.0)


def test_half_of_the_batch_left_out(monkeypatch, capsys):
    def wrap(step):
        def half(params, opt_state, batch):
            t = batch["tokens"]
            return step(params, opt_state, {"tokens": t[:t.shape[0] // 2]})
        return half

    break_train_step(monkeypatch, wrap)
    line = run_cell(capsys, TRAIN, "tiny_train.json")
    assert line["correct"] is False
    assert "grad_norm_gap_worst_leaf" in failing(line)


def test_token_altered_where_it_is_produced(monkeypatch, capsys):
    from ray_tpu.models import inference

    real = inference.decode_chunk

    def altered(params, cfg, *args, **kw):
        outs, toks, lens, k, v = real(params, cfg, *args, **kw)
        # the first step's token of every slot, as handed to the client
        outs = outs.at[0].set((outs[0] + 1) % cfg.vocab_size)
        return outs, toks, lens, k, v

    monkeypatch.setattr(inference, "decode_chunk", altered)
    line = run_cell(capsys, SERVE, "tiny_serve.json")
    assert line["correct"] is False
    assert failing(line) == ["widest_logit_gap"]


def test_stream_cut_short(monkeypatch, capsys):
    """An answer that never comes in full is a failed request."""
    from ray_tpu.serve import llm

    real = llm.LLMDeployment._cls.start_stream

    def short(self, prompt, max_new_tokens=None):
        return real(self, prompt, max(1, (max_new_tokens or 2) - 1))

    monkeypatch.setattr(llm.LLMDeployment._cls, "start_stream", short)
    with pytest.raises(RuntimeError, match="warm-up requests failed"):
        run_cell(capsys, SERVE, "tiny_serve.json")


def test_jitted_function_first_used_inside_the_window(monkeypatch, capsys):
    """A jitted function that set-up did not build shows inside the
    window by its name, compiled or read from the cache, however short
    it took: the run fails and prints no result."""
    from benchmark import compile_watch

    monkeypatch.setattr(
        compile_watch.CompileWatch, "between",
        lambda self, t0, t1: [("jit(concatenate)", 0.04),
                              ("jit(<lambda>)", 0.002)])
    with pytest.raises(compile_watch.CompiledInWindow, match="<lambda>"):
        run_cell(capsys, SERVE, "tiny_serve.json")


def test_eager_primitive_stretched_or_cached_is_no_fault(monkeypatch,
                                                         capsys):
    """The engine's eager concatenations are the program's own cost:
    one that a stalled host stretched to seconds, or that an earlier
    run left in the persistent cache, fails nothing (the driver's check
    of PR 24 met one and the old gate, which went by seconds and cache
    hits, ended the run with code 1)."""
    from benchmark import compile_watch

    monkeypatch.setattr(
        compile_watch.CompileWatch, "between",
        lambda self, t0, t1: [("jit(concatenate)", 3.2),
                              ("jit(concatenate)", 0.004)])
    monkeypatch.setattr(compile_watch.CompileWatch, "hits_between",
                        lambda self, t0, t1: 1)
    line = run_cell(capsys, SERVE, "tiny_serve.json")
    assert line["correct"] is True


@pytest.mark.parametrize("name,eager", [
    ("jit(concatenate)", True), ("jit(reshape)", True),
    ("jit(dynamic_slice)", True), ("jit(<lambda>)", False),
    ("jit(<unknown>)", False), ("jit(prefill_write_many)", False),
    ("jit(_split_packed)", False), ("concatenate", False), ("?", False)])
def test_eager_primitive_by_name(name, eager):
    from benchmark import compile_watch

    assert compile_watch.eager_primitive(name) is eager
