"""The benchmark's own tests, run by explicit path on the CPU:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

#: settings that a test run can hold on the CPU (the cells' own sizes run
#: on the chip)
TINY = dict(clients=8, samples_per_client=16, train_samples=200,
            test_samples=80, local_iters=2, batch_size=8, rounds=2,
            devices_per_round=4, num_clusters=4)


#: the smallest settings tried at which a round that keeps the global
#: model, or drops half of its clients from eq. (4), moves the accuracy
#: past ``acc_mean_gap``'s limit (at ``TINY`` they train too little to)
FAULT_SIZE = dict(TINY, clients=16, devices_per_round=8, num_clusters=8,
                  local_iters=10, rounds=10, samples_per_client=64,
                  train_samples=1600, test_samples=400, batch_size=16)


def pytest_configure(config):
    """No persistent compile cache in the tests: the CPU's programs are of
    no use to a run on the chip, and would only fill the checkout's."""
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
