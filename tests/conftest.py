"""Test config: force CPU backend with 8 virtual devices so sharding /
distributed tests run without TPU hardware (SURVEY.md §4 takeaway #5 —
fake-device testing of collective plumbing; the reference uses
multi-process-on-one-host, we use XLA's virtual host devices).
"""
import os
import sys


def force_virtual_devices(n: int = 8) -> None:
    """The multi-device CPU emulation used by the MULTICHIP benches,
    benchmarks/run_all.py and this test suite, in ONE place: force the
    CPU backend and ``n`` virtual XLA host devices. MUST run before
    jax initializes a backend — import-time here; benchmarks call
    their own copy of this dance before importing jax (they cannot
    import tests/conftest). No-op when an XLA_FLAGS device count is
    already pinned, so nesting (pytest -> subprocess bench -> this)
    keeps the outer setting."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = \
            flags + f" --xla_force_host_platform_device_count={n}"


force_virtual_devices(8)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


# ---------------------------------------------------------------------------
# Test tiers (round-5 verdict #10): `pytest -m "not full" tests/` is the
# SMOKE tier (~5 min on a 1-core host — every subsystem touched once);
# the unmarked default runs everything (>50 min on 1 core). Files listed
# here auto-receive the `full` marker: e2e/multi-process suites, big op
# matrices, and numerics batteries whose value is breadth, not speed.
# ---------------------------------------------------------------------------
import pytest  # noqa: E402

_FULL_TIER_FILES = {
    # multi-process / e2e orchestration
    "test_elastic_e2e.py", "test_multiproc_checkpoint.py",
    "test_dist_model_mp.py", "test_bert_distmodel.py",
    "test_dataloader_workers.py", "test_incubate_multiprocessing.py",
    "test_ps_ssd_graph.py", "test_store_rpc.py",
    # big model-level suites (minutes each on 1 core)
    "test_moe_gpt.py", "test_llama.py", "test_ppyoloe.py",
    "test_vision_models.py", "test_auto_capture_zoo.py",
    "test_download_pretrained.py",
    # op matrices / numerics batteries
    "test_op_suite.py", "test_op_suite_nn_linalg.py",
    "test_op_rows_extras.py", "test_ops_extras.py",
    "test_nn_extras.py", "test_distribution_numeric.py",
    "test_distribution_grads.py", "test_rnn_numeric.py",
    # pipeline schedule batteries (every schedule x factorization)
    "test_pipeline_scheduled.py", "test_pipeline_schedules.py",
    "test_pipeline_1f1b.py", "test_reshard_transitions.py",
    # compile-heavy
    "test_scaling_model.py", "test_benchmarks_smoke.py",
    "test_sot_partial.py", "test_quant_pallas.py",
    # measured >30s each on the 1-core host (--durations, r5)
    "test_fft_signal_utils.py", "test_baseline_configs.py",
    "test_int8_guard.py", "test_fused_ce.py",
    "test_fuse_ln_modes.py",
}


# ---------------------------------------------------------------------------
# Shared multi-device helpers (import in test files: `from conftest
# import require_devices, serving_model_mesh`): mesh-sharded serving
# tests ride the SAME 8 virtual devices forced above — a guarded skip
# instead of a hard failure keeps the suite honest on images where the
# emulation is unavailable, without polluting single-device tests
# (programs not built under a mesh still place on device 0 only).
# ---------------------------------------------------------------------------

def require_devices(n: int):
    """Skip the calling test unless >= n (virtual) devices exist."""
    if jax.device_count() < n:
        pytest.skip(f"needs {n} devices, have {jax.device_count()} "
                    f"(XLA host-device emulation not active)")


def serving_model_mesh(tp: int = 2, prefill: int = 0):
    """A ProcessMesh with a `model` axis over ``tp + prefill``
    devices, for ServingEngine(mesh=...) tests: the first ``prefill``
    devices become the disaggregated prefill group when the engine is
    built with prefill_devices=prefill."""
    require_devices(tp + prefill)
    import numpy as _np

    from paddle_tpu.distributed import ProcessMesh
    return ProcessMesh(_np.arange(tp + prefill), ["model"])


def model_greedy(model, prompt, max_new: int):
    """The model's own greedy decode of one prompt, the serving tests'
    reference that is independent of the engine: the public
    ``generate()`` where the family has one (llama), else (GPT) a
    cache-free loop that re-runs the FULL sequence every step and
    takes the argmax at its last position (the sequence sits in one
    buffer of the model's position range, so every step is one shape:
    what lies behind a position cannot reach it through a causal
    mask)."""
    import numpy as _np

    import paddle_tpu as paddle
    prompt = _np.asarray(prompt, _np.int64)
    n = len(prompt)
    if hasattr(model, "generate"):
        return model.generate(
            paddle.to_tensor(prompt[None]),
            max_new_tokens=max_new).numpy()[0, n:].tolist()
    ids = _np.zeros((1, model.cache_spec().max_positions), _np.int64)
    ids[0, :n] = prompt
    for i in range(n, n + max_new):
        logits = model(paddle.to_tensor(ids)).numpy()
        ids[0, i] = int(_np.argmax(logits[0, i - 1]))
    return ids[0, n:n + max_new].tolist()


@pytest.fixture(scope="module")
def worker_compile_cache(tmp_path_factory):
    """serving/worker.py turns the compile cache on: modules that spawn
    cluster worker processes (which inherit os.environ) opt in with
    ``pytestmark = pytest.mark.usefixtures("worker_compile_cache")`` so
    the workers keep theirs out of the checkout. The test process's own
    jax config is untouched — JAX read the variable at import."""
    mp = pytest.MonkeyPatch()
    mp.setenv("JAX_COMPILATION_CACHE_DIR",
              str(tmp_path_factory.mktemp("jax_cache")))
    yield
    mp.undo()


# shared interpreter-version gates (import in test files:
# `from conftest import needs_monitoring, needs_311_bytecode`)
needs_monitoring = pytest.mark.skipif(
    not hasattr(sys, "monitoring"),
    reason="jit.auto_capture rides sys.monitoring (CPython 3.12+)")
needs_311_bytecode = pytest.mark.skipif(
    sys.version_info < (3, 11),
    reason="SOT bytecode executor targets the CPython 3.11+ opcode "
           "set; older interpreters take the eager fallback")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "full: slow/e2e tests excluded from the smoke tier "
        "(run smoke with -m 'not full')")
    config.addinivalue_line(
        "markers",
        "chaos: fast fault-injection/recovery tests (tier-1 by "
        "design; run the subset alone with -m chaos)")


def pytest_collection_modifyitems(config, items):
    for item in items:
        if os.path.basename(str(item.fspath)) in _FULL_TIER_FILES:
            item.add_marker(pytest.mark.full)
