"""A whole run of each cell at a tiny size on the CPU (the harness's look
for a chip skipped), sound and with the timed path broken underneath:
every planted fault must turn ``correct`` false."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.tests.tiny import tiny_cell

TRAIN = ["qwen1.5-0.5b.train", "qwen3-8b.train_tp4"]
ACROSS_CHIPS = ["qwen3-8b.train_tp4"]
SERVE = ["qwen1.5-0.5b.serve_backlog"]


def run(cell):
    """The whole run after the look for a chip (``bench.run.execute``);
    the result line's ``correct`` must be the job's."""
    from bench.run import CHECKOUT, execute, load_json

    out, res = execute(cell, load_json(CHECKOUT / "BENCHMARK.json"),
                       load_json(CHECKOUT / "bench" / "peaks.json")
                       ["TPU v5 lite"])
    assert out["correct"] == res.correct
    assert list(out)[-1] == "checks"
    return res


def patch_train_step(monkeypatch, wrap):
    import repro.launch.train as lt

    real = lt.build_train_step

    def build(*a, **k):
        step, info = real(*a, **k)
        return wrap(step), info

    monkeypatch.setattr(lt, "build_train_step", build)


@pytest.mark.parametrize("cell", TRAIN + SERVE)
def test_sound_run_is_correct(cell):
    res = run(tiny_cell(cell))
    assert res.correct, res.checks
    assert res.attempted > 0 and res.failed == 0


@pytest.mark.parametrize("cell", TRAIN)
def test_state_left_unchanged(monkeypatch, cell):
    def wrap(step):
        def unchanged(p, o, b):
            copy = lambda t: jax.tree.map(jnp.copy, t)  # noqa: E731
            _, _, metrics = step(copy(p), copy(o), b)
            return p, o, metrics
        return unchanged

    patch_train_step(monkeypatch, wrap)
    res = run(tiny_cell(cell))
    assert not res.correct, res.checks


@pytest.mark.parametrize("cell", TRAIN)
def test_half_batch_left_out(monkeypatch, cell):
    def wrap(step):
        def half(p, o, b):
            return step(p, o, {k: v[: v.shape[0] // 2] for k, v in b.items()})
        return half

    patch_train_step(monkeypatch, wrap)
    res = run(tiny_cell(cell))
    assert not res.correct, res.checks


@pytest.mark.parametrize("cell", SERVE)
def test_token_altered_where_produced(monkeypatch, cell):
    import repro.launch.serve as ls

    real = ls._build_paged_step_fn

    def build(cfg, *a, **k):
        step_fn, init, info = real(cfg, *a, **k)
        calls = [0]

        def altered(tokens, start, table, caches):
            toks, caches = step_fn(tokens, start, table, caches)
            calls[0] += 1
            if tokens.shape[1] == 1 and calls[0] % 5 == 0:
                toks = (np.asarray(toks) + 1) % cfg.vocab_size
            return toks, caches

        return altered, init, info

    monkeypatch.setattr(ls, "_build_paged_step_fn", build)
    res = run(tiny_cell(cell))
    assert not res.correct, res.checks


@pytest.mark.parametrize("cell", ACROSS_CHIPS)
def test_exchange_left_out(cell):
    from bench.faults import exchange_left_out

    with exchange_left_out():
        res = run(tiny_cell(cell))
    assert not res.correct, res.checks


@pytest.mark.parametrize("cell", TRAIN[:1] + SERVE)
def test_traced_run_reads_its_layers(cell):
    """A ``--trace 1`` run on the CPU: every per-layer metric the cell
    lists that a CPU trace can carry comes back, with a breakdown."""
    from bench.run import CHECKOUT, execute, load_json

    c = tiny_cell(cell)
    c.trace = True
    bench = load_json(CHECKOUT / "BENCHMARK.json")
    out, res = execute(c, bench, load_json(CHECKOUT / "bench" / "peaks.json")
                       ["TPU v5 lite"])
    assert res.correct, res.checks
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    assert out["breakdown"]["device_ops"]
    want = {"qwen1.5-0.5b.train": {"device_idle_share.train", "mfu.train"},
            "qwen1.5-0.5b.serve_backlog": {"device_idle_share.serve",
                                           "decode_step_ms.serve",
                                           "mfu.serve"}}[cell]
    assert want <= set(out["metrics"]), out["metrics"]
    assert all(0 <= m["value"] <= 100 for n, m in out["metrics"].items()
               if m["unit"] == "%")
