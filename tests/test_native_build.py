"""The two native libraries build to a name of the builder's own and are
renamed onto the final one: processes that start together on a checkout
whose `native/` holds no library (git ignores `native/*.so`) all load
both, whoever builds.  Before, `g++` wrote straight to the final name, a
second process found the half-written file, failed to load it and kept
`None` for its lifetime (24 cluster rehearsals lost under `-n 6`)."""
import os
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LOAD = """
import json, sys
sys.path.insert(0, sys.argv[1])
from yugabyte_db_tpu.docdb import hotpath
from yugabyte_db_tpu.storage import native_lib
print(json.dumps({"hot": hotpath.load() is not None,
                  "native": native_lib.available(),
                  "dir": hotpath._NATIVE_DIR,
                  "errors": [hotpath.last_build_error,
                             native_lib.last_build_error]}))
"""


def test_processes_that_start_on_an_empty_native_dir_all_load(tmp_path):
    if shutil.which("g++") is None:
        import pytest
        pytest.skip("no g++: the pure-Python fallbacks serve")
    # the package by a link, so that its `native/` is the copy's, which
    # holds the two sources and no library
    os.symlink(os.path.join(ROOT, "yugabyte_db_tpu"),
               tmp_path / "yugabyte_db_tpu")
    os.mkdir(tmp_path / "native")
    for src in ("ybtpu_hot.c", "ybtpu_native.cpp"):
        shutil.copy(os.path.join(ROOT, "native", src), tmp_path / "native")
    env = dict(os.environ, JAX_PLATFORMS="cpu", YBTPU_PLATFORM="cpu")
    procs = []
    for _ in range(4):
        # staggered: the later ones arrive while the first still compiles
        procs.append(subprocess.Popen(
            [sys.executable, "-c", LOAD, str(tmp_path)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
        time.sleep(0.4)
    import json
    for p in procs:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, err[-2000:]
        got = json.loads(out.strip().splitlines()[-1])
        assert got["dir"] == str(tmp_path / "native")
        assert got["hot"] and got["native"], got
    left = sorted(os.listdir(tmp_path / "native"))
    assert [f for f in left if f.endswith(".tmp")] == []
    assert len([f for f in left if f.endswith(".so")]) == 2
