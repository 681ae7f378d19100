"""compile_v5e.compare: two compiles that differ only in names and
source locations hold the same instructions; any other change shows."""
import gzip
import os
import re

import compile_v5e

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "tiny_v5e_scoped.hlo.txt.gz")


def _text():
    with gzip.open(DATA, "rt") as f:
        return f.read()


def test_names_and_locations_are_stripped():
    text = _text()
    unscoped = re.sub(r"/als_mode\d+/(mttkrp|solve|remap)", "", text)
    moved = re.sub(r"source_line=(\d+)",
                   lambda m: f"source_line={int(m.group(1)) + 6}", unscoped)
    assert moved != text
    assert compile_v5e.instructions(moved) == compile_v5e.instructions(text)
    # The kernels' serialized bodies are decoded, not compared as bytes.
    assert any("tpu_custom_call" in line
               for line in compile_v5e.instructions(text))


def test_a_changed_instruction_shows():
    text = _text()
    changed = text.replace("= s32[4000]", "= s32[4001]", 1)
    assert changed != text
    old, new = (compile_v5e.instructions(t) for t in (text, changed))
    assert sum(a != b for a, b in zip(old, new)) == 1
