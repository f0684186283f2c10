"""A compiled step's identity is its computation.

A Pallas kernel's body is serialized into its custom call, and the
compile cache keys a step by it. Were a Python frame in it, a comment
line added above any caller of a kernel, or a checkout moved to
another directory, would be a cold compile of every cell, and no
refactor could show "the lowered steps hash equal".
``trainer/jax_env.enable_compile_cache`` leaves no frame in any
location; these tests lower the Kimi cell's step (every layer in
line, 68 kernels), OLMoE's and GPT-2's for a described ``v5e:2x2``,
compile nothing, and hold them to that.
"""

import json
import os
import sys

import jax
import pytest

from dlrover_tpu.trainer.jax_env import enable_compile_cache
from tests.tpu_steps import (  # noqa: F401 — the fixtures
    STEPS,
    compiled_kernels,
    lower_step,
    topo,
)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import step_hash  # noqa: E402

NAMES = ("kimi", "olmoe", "gpt2")
_OPTIONS = (
    "jax_traceback_in_locations_limit",
    "jax_compilation_cache_dir",
    "jax_persistent_cache_min_compile_time_secs",
)


@pytest.fixture
def jax_options_restored():
    before = {name: getattr(jax.config, name) for name in _OPTIONS}
    yield
    for name, value in before.items():
        jax.config.update(name, value)


@pytest.fixture
def as_the_program_sets_jax(jax_options_restored):
    """JAX's options as every process that lowers a step for the chip
    has them."""
    enable_compile_cache()


@pytest.mark.parametrize("owner", ["the caller's directory", "the checkout's"])
def test_no_frame_is_kept_on_either_path(tmp_path, jax_options_restored, owner):
    theirs = str(tmp_path / "cache") if owner.startswith("the caller") else None
    jax.config.update("jax_compilation_cache_dir", theirs)
    jax.config.update("jax_traceback_in_locations_limit", 10)
    assert (enable_compile_cache() == theirs) == (theirs is not None)
    assert jax.config.jax_traceback_in_locations_limit == 0


def _called_from_line(lines: int, attn_fn):
    """``attn_fn`` behind a function defined ``lines`` lines down a
    file of its own: the frame next above the flash kernels' entry."""
    source = "\n" * lines + (
        "def attention(q, k, v, **kw):\n    return inner(q, k, v, **kw)\n"
    )
    scope = {"inner": attn_fn}
    exec(compile(source, f"<{lines} lines down>", "exec"), scope)
    return scope["attention"]


@pytest.fixture(scope="module")
def text_of(topo):
    """(name, lines) -> the step's lowered text, its attention called
    from that line; each lowered once for the tests of this file."""
    texts = {}

    def text(name, lines=0):
        if (name, lines) not in texts:
            model, cfg = STEPS[name][0], STEPS[name][1]()
            attn_fn = _called_from_line(
                lines, model.default_attention_for(cfg)
            )
            texts[name, lines] = lower_step(name, topo, attn_fn).as_text()
        return texts[name, lines]

    return text


@pytest.mark.parametrize("name", NAMES)
def test_no_kernel_body_names_a_source_file(
    text_of, compiled_kernels, as_the_program_sets_jax, name
):
    said = step_hash.describe(text_of(name))
    assert said["custom_calls"] >= 4, said
    assert said["bodies_naming_a_file"] == 0, said


@pytest.mark.parametrize("name", NAMES)
def test_a_step_is_the_same_text_from_any_line(
    text_of, compiled_kernels, as_the_program_sets_jax, name
):
    """The step built through a call site on line 1 of a file and
    through one on line 8 of another is one text."""
    assert text_of(name, lines=7) == text_of(name)


def test_a_step_is_the_same_text_after_another(
    topo, compiled_kernels, as_the_program_sets_jax
):
    """OLMoE's step lowered first in a process and lowered after
    Kimi's, whose held expert path traces the same grouped kernels
    through other callers: a kernel's traced body stays in JAX's
    caches with the frames of whoever traced it first."""
    jax.clear_caches()
    alone = lower_step("olmoe", topo).as_text()
    jax.clear_caches()
    lower_step("kimi", topo)
    assert lower_step("olmoe", topo).as_text() == alone


def test_the_tool_prints_a_line_a_step(
    topo, compiled_kernels, monkeypatch, capsys, as_the_program_sets_jax
):
    monkeypatch.setenv("ONLY", "mistral")
    assert step_hash.main() == 0
    (line,) = capsys.readouterr().out.splitlines()
    said = json.loads(line)
    # One Mosaic call in the lowered text for each distinct traced call
    # of a kernel: the flash forward and backward, and since PR 62 the
    # rotation (q and k, forward and on the cotangents; the second
    # layer's are the first's again) and the group sums.
    assert said["step"] == "mistral" and said["custom_calls"] == 7
    assert said["bodies_naming_a_file"] == 0
    assert len(said["sha256"]) == 64 and said["bytes"] > 100_000
    assert said == {
        "step": "mistral",
        **step_hash.describe(lower_step("mistral", topo).as_text()),
    }
