"""The gate's own guards: tests/conftest.py's count of the process's memory
maps (a worker that reaches `vm.max_map_count` dies inside the compiler:
PERF.md section 7, "Found (PR 59)") and tests/serving_script.py's one
program a (family, config, tree form)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.inference import PagedKVCache
from ray_tpu.models import gpt
from tests import conftest, serving_script


def test_the_reader_counts_this_processes_maps_or_says_it_cannot(
        monkeypatch, tmp_path):
    held, limit = conftest.memory_maps()
    assert 0 < held < limit
    monkeypatch.setattr(conftest, "MAPS", str(tmp_path / "no_proc_here"))
    assert conftest.memory_maps() is None
    conftest.release_programs_past_half_the_map_limit()     # and does nothing


def test_a_process_past_half_the_limit_gives_its_programs_back(monkeypatch,
                                                               tmp_path):
    """A limit of this process's own count and 600 more: it compiles
    programs until it holds more than half of that, and what a test's
    teardown runs brings it back under."""
    held, _ = conftest.memory_maps()
    low = tmp_path / "max_map_count"
    low.write_text(f"{2 * (held + 300)}\n")
    monkeypatch.setattr(conftest, "MAP_LIMIT", str(low))
    programs = []
    while conftest.memory_maps()[0] <= held + 300:
        programs.append(jax.jit(lambda x, n=len(programs): x * n + 1))
        programs[-1](jnp.ones(3))
        assert len(programs) < 2000
    grown, limit = conftest.memory_maps()
    assert 2 * grown > limit
    conftest.release_programs_past_half_the_map_limit()
    assert 2 * conftest.memory_maps()[0] <= limit
    np.testing.assert_array_equal(programs[2](jnp.ones(3)), 3 * np.ones(3))


def test_the_serving_scripts_step_is_one_program_a_family_config_and_tree():
    """Two runs of one token a slice: one entry more in the step's cache,
    not one a slice and not one a run."""
    cfg = dataclasses.replace(gpt.CONFIGS["nano"], vocab_size=384)
    params = gpt.init_params(cfg, jax.random.key(0))
    tokens = np.arange(5) % cfg.vocab_size
    before = serving_script.step._cache_size()
    runs = []
    for _ in range(2):
        cache = PagedKVCache.for_model(gpt, cfg, num_blocks=4, block_size=8,
                                       max_lanes=1, max_seq_len=16)
        (logits,), _, _ = serving_script.serve(
            gpt, cfg, params, cache, [tokens], 1, [0], prefill=[0])
        runs.append(logits)
    assert serving_script.step._cache_size() == before + 1
    np.testing.assert_array_equal(*runs)
    assert runs[0].shape == (5, cfg.vocab_size)
