"""The benchmark's own tests run on the CPU, whatever the machine holds, and
keep CPU executables out of the checkout's compile cache."""
import os
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(tempfile.gettempdir(), "bench_tests_jax_cache"))
