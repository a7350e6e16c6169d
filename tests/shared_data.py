"""The SF0.01 raw data the test modules share, generated once per temporary
directory.

xdist workers that meet a cold temporary directory all want the data at
once. The first to take the lock generates into a directory beside the
target and renames that into place; the others wait on the lock and find it
there. Nothing is ever written at the target itself, so a directory that
exists is complete: nobody reads a half-written table and nobody's
`--overwrite_output` runs under another worker's readers.

Everything lives under `tempfile.gettempdir()` (so a run with a `TMPDIR` of
its own has a copy of its own) in a directory that only this helper writes:
a tree from before it, which generates straight into `/tmp/nds_test_sf001`
without a lock, never meets these paths, and nothing here deletes a path
that another tree's run may be writing."""

import fcntl
import os
import shutil
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.join(tempfile.gettempdir(), "nds_tpu_tests")
DATA = os.path.join(ROOT, "sf001")
REFRESH = os.path.join(ROOT, "sf001_refresh")


def _generated(path, *extra):
    if os.path.isdir(path):
        return path
    parent, name = os.path.split(path)
    os.makedirs(parent, exist_ok=True)
    with open(path + ".lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if os.path.isdir(path):
            return path
        tmp = tempfile.mkdtemp(prefix=name + ".", dir=parent)
        try:
            subprocess.run(
                [sys.executable, "-m", "nds_tpu.cli.gen_data", "--scale",
                 "0.01", "--parallel", "2", "--data_dir", tmp, *extra,
                 "--overwrite_output"],
                check=True, capture_output=True, cwd=REPO,
            )
            os.rename(tmp, path)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    return path


def raw_data():
    """The SF0.01 source tables (`gen_data --scale 0.01`)."""
    return _generated(DATA)


def refresh_data():
    """The SF0.01 refresh set (`gen_data --scale 0.01 --update 1`)."""
    return _generated(REFRESH, "--update", "1")
