"""A described TPU v5e:2x2 for ahead-of-time compiles without the chip,
the fixture test_aot_compile.py (kernels and layers) and
test_aot_steps.py (whole train steps) share.  Not collected by pytest: no
``test_`` prefix."""
import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # or libtpu logs to /tmp

import jax                                        # noqa: E402


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2; the persistent compile cache is off around
    these compiles (an entry written for a described device cannot be
    read back without one, and the retry warns)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        described = topologies.get_topology_desc(platform="tpu",
                                                 topology_name="v5e:2x2")
    except Exception as e:      # no libtpu here: nothing to compile with
        pytest.skip("cannot describe a v5e topology: %s" % e)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield described
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()
