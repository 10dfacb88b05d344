"""The check that decides ``correct``, driven through a whole run at a size
a test run can hold (the harness's look for a chip skipped, the kernels'
jnp oracles in place of the compiled ones): a sound run is correct; the
control, and a served path broken underneath, are not."""
import pytest

import bench_tiny
from chipbench import harness

SECONDS = 2.0
VOCAB = 256


def _run(seed, fault=None, control=False):
    cell = bench_tiny.tiny_cell()
    res, compared = harness.run(cell, seed, SECONDS, False, backend="ref",
                                control=control, fault=fault,
                                log=lambda m: None)
    return res, dict((n, v) for n, v, _ in compared)


def alter_tokens(server):
    """Every eighth token is altered where the engine emits it."""
    emit, count = server._emit_token, [0]

    def altered(req, token):
        count[0] += 1
        emit(req, (token + 1) % VOCAB if count[0] % 8 == 0 else token)

    server._emit_token = altered


def keep_state(server):
    """The engine step returns its page pool unchanged."""
    step = server._decode

    def unchanged(params, pools, *a, **k):
        nxt, ok, _ = step(params, pools, *a, **k)
        return nxt, ok, pools

    server._decode = unchanged


def test_sound_run_is_correct_and_the_control_is_not():
    res, got = _run(2 ** 31 + 11)
    assert res["correct"] is True
    assert got["mean_gap"] <= bench_tiny.TINY_LIMIT
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"ttft_p90_ms", "itl_p95_ms", "setup_s"}
    assert list(res)[-1] == "check"
    # the control in the program's place, on the same seed and traffic
    ctl, got = _run(2 ** 31 + 11, control=True)
    assert ctl["correct"] is False
    assert list(ctl["check"]) == ["control.mean_gap"]
    assert got["control.mean_gap"] > bench_tiny.TINY_LIMIT


@pytest.mark.parametrize("fault", [alter_tokens, keep_state],
                         ids=["token_altered", "state_unchanged"])
def test_a_broken_served_path_is_not_correct(fault):
    res, got = _run(2 ** 31 + 12, fault=fault)
    assert res["correct"] is False
    assert got["mean_gap"] > bench_tiny.TINY_LIMIT
