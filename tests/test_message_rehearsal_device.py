"""The start -> message catch -> end rehearsal of
``zbench/tests/test_message_rehearsal.py`` (the process and the copy of the
harness to which only new files are added come from there) on the DEVICE
engine, with every message published as soon as its create is acknowledged
(``delay_ms`` 0), on the seeds on which a PUBLISH and its subscription's
OPEN met in one wave and the order never completed before the device
engine cut such waves in two (``PERF.md``, Open questions 0)."""

import json
import os
import subprocess
import sys

import pytest

from zbench.tests.test_message_rehearsal import CELL, _copy_with_new_files


@pytest.mark.parametrize("seed", [2147483900, 2147483937])
def test_the_device_engine_correlates_a_message_published_at_once(tmp_path, seed):
    copy = _copy_with_new_files(tmp_path, 0)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    run = subprocess.run(
        [sys.executable, "-m", "zbench", "--workload", CELL, "--seed", str(seed),
         "--seconds", "4", "--trace", "0", "--rehearsal"],
        cwd=copy, env=env, capture_output=True, text=True, timeout=600,
    )
    assert run.returncode == 0, run.stderr[-2000:]
    lines = run.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True, result["compared"]
    assert all(v == [0, 0] for v in result["compared"].values()), result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    report = next(json.loads(line) for line in lines if line.startswith('{"zbench": "run"'))
    # served by the device engine: no instance or message-family record
    # went to the host engine
    assert not any(
        kind.startswith(("vt5/", "vt11/", "vt12/", "vt13/"))
        for kind in report["engine"]["host_records_by_kind"]
    ), report["engine"]
    assert report["engine"]["device_records"] > 0
