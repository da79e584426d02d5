"""Build hook for the native CPU reducer.

The reference's setup.py (865 LoC) compiles three framework C++ extensions
against the common core (reference setup.py:235-271, with NCCL/RDMA/MPI
probing).  The TPU build needs none of that — XLA owns the device path —
but the host-side OpenMP reducer (csrc/byteps_native.cc, the cpu_reducer.cc
analog used by the async-PS server tier) is compiled here when a toolchain
exists.  Failure is non-fatal: byteps_tpu/native/reducer.py also builds on
first use and falls back to numpy.
"""

import os
import subprocess

from setuptools import setup
from setuptools.command.build_py import build_py


class BuildWithNative(build_py):
    def run(self):
        here = os.path.dirname(os.path.abspath(__file__))
        srcs = [
            os.path.join(here, "csrc", "byteps_native.cc"),
            os.path.join(here, "csrc", "data_loader.cc"),
        ]
        srcs = [s for s in srcs if os.path.exists(s)]
        out = os.path.join(here, "byteps_tpu", "native", "libbyteps_native.so")
        if srcs:
            cmd = [
                os.environ.get("CXX", "g++"),
                "-O3", "-fopenmp", "-pthread", "-fPIC",
                "-std=c++17", "-shared", "-o", out, *srcs,
            ]
            try:
                subprocess.run(cmd, check=True, capture_output=True,
                               timeout=300)
                print(f"built native reducer: {out}")
            except Exception as e:  # non-fatal: runtime numpy fallback
                print(f"native reducer build skipped ({e}); "
                      "numpy fallback will be used")
        super().run()


setup(cmdclass={"build_py": BuildWithNative})
