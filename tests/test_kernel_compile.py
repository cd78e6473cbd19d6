"""Nothing ``_pallas_eligible`` admits may be refused by the v5e compiler.

Runs ``tests/kernel_compile_worker.py`` (compile-only Mosaic through
libtpu's topology client — no chip, seconds) in a subprocess: both
A-build variants x batch buckets on every step of the tile schedule x
widths from the ELL ladder incl. 12, a mesh-split width of 1 and an odd
one, plus the (4, 1) ``make_mesh_ell_search`` program. Interpret-mode
parity (``tests/test_kernel_parity.py``) cannot see what this sees: a
kernel the interpreter runs happily and Mosaic rejects.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest


@pytest.mark.skipif(importlib.util.find_spec("libtpu") is None,
                    reason="libtpu (the compile-only TPU client) is "
                           "not installed")
def test_every_eligible_shape_compiles_for_v5e():
    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "kernel_compile_worker.py")
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run([sys.executable, worker], env=env,
                       capture_output=True, text=True, timeout=600)
    lines = p.stdout.strip().splitlines()
    assert lines, f"no report (rc={p.returncode}):\n{p.stderr[-2000:]}"
    report = json.loads(lines[-1])
    assert not report["failures"], "\n".join(report["failures"])
    assert p.returncode == 0
    # a run that compiled nothing proves nothing
    assert report["compiled"] >= 70, report
