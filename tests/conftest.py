# NOTE: no XLA_FLAGS here on purpose — smoke tests and benches must see the
# single real CPU device; only launch/dryrun.py forces 512 host devices.
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.utils.compile_cache import configure_compile_cache  # noqa: E402

# persistent compile cache (pure speed-up; before the first compile)
configure_compile_cache()
