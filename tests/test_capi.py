"""Build and run the C ABI test program (automerge_tpu/capi).

The reference ships a C frontend exercised by cmocka suites
(reference: automerge-c/test/); here the cdylib embeds the Python
runtime and the C program drives create/edit/save/load/merge/sync
through am.h alone.
"""

from __future__ import annotations

import os
import shutil
import subprocess

import pytest

from automerge_tpu import capi


@pytest.mark.skipif(
    shutil.which("g++") is None or shutil.which("gcc") is None,
    reason="no C/C++ toolchain",
)
@pytest.mark.parametrize("source", capi.TEST_SOURCES)
def test_c_abi_end_to_end(tmp_path, source):
    lib = capi.build()
    assert lib is not None, "cdylib build failed"
    exe = capi.build_test(lib, str(tmp_path), source=source)
    assert exe is not None, f"C test program build failed ({source})"
    env = dict(os.environ)
    # the embedded interpreter stays on the CPU backend, as every test does
    env["JAX_PLATFORMS"] = "cpu"
    env["AUTOMERGE_TPU_PYROOT"] = capi._REPO_ROOT
    r = subprocess.run(
        [exe], capture_output=True, text=True, timeout=300, env=env
    )
    assert r.returncode == 0, f"stdout: {r.stdout}\nstderr: {r.stderr}"
    assert "all assertions passed" in r.stdout
