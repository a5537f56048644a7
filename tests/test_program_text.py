"""The accepted cells' programs are the ones `tests/data/program_text.json` records.

The file holds, from the last commit that meant to change them, a hash of the
text every accepted serving cell's decode step and first and last prefill
bucket, Mistral's one-device train step and the attention kernels lower to for
the TPU at the published widths (`scripts/program_text.py`).  A change that
adds a configuration beside them leaves every one as it was, and then nothing
of theirs can move on the chip; a change that means to move one writes the file
anew, and the file's diff says which cells to measure."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "tests", "data", "program_text.json")) as f:
    RECORDED = json.load(f)


@pytest.fixture(scope="module")
def lowered(tmp_path_factory):
    # a process of its own: the script points the attention dispatchers at the TPU's kernels
    out = tmp_path_factory.mktemp("program_text") / "now.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run([sys.executable, os.path.join(ROOT, "scripts", "program_text.py"), ROOT, str(out)],
                          env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    with open(out) as f:
        return json.load(f)


def test_every_recorded_program_is_lowered(lowered):
    assert set(lowered) == set(RECORDED)


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_the_program_lowers_to_the_recorded_text(lowered, name):
    assert lowered[name] == RECORDED[name], (
        f"{name} lowers to another program than tests/data/program_text.json records: if the change means to, "
        f"write the file anew (python3 scripts/program_text.py . tests/data/program_text.json) and measure the cell")
