"""Test environment: force JAX onto a virtual 8-device CPU mesh.

Chip runs go through chip_smoke.py and bench.py; tests are hermetic and
exercise the multi-chip sharding path, so we ask XLA for 8 host devices.
Must run before jax is imported anywhere.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# Override, don't setdefault: a chip machine's environment may name the
# TPU, but unit tests need the virtual 8-CPU mesh.  jax may already be
# imported (a plugin), and its config wins over the env var.
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# Persistent compilation cache (see ceph_tpu.arch): XLA compiles dominate
# the crush device/fast suites; with the on-disk cache warm the tier-1
# suite fits its wall budget with room to spare.
from ceph_tpu.arch import configure_compile_cache  # noqa: E402

configure_compile_cache()


def pytest_runtest_protocol(item, nextitem):
    """Single auto-rerun for ``@pytest.mark.loadflaky`` tests.

    The two vstart thrash tests are known to flake ONLY under
    concurrent CPU load (verified pre-existing at their parent
    commits: both pass in isolation and in green full-suite runs) —
    their mon kill/revive event-waits time out when the box is
    oversubscribed.  One retry on a FRESH cluster (all fixtures torn
    down, module-scoped ProcessCluster included, so the rerun doesn't
    inherit a wedged quorum) keeps pre-existing load flakes from
    masking real regressions; a deterministic failure still fails
    twice and surfaces."""
    if item.get_closest_marker("loadflaky") is None:
        return None
    from _pytest.runner import runtestprotocol
    item.ihook.pytest_runtest_logstart(nodeid=item.nodeid,
                                       location=item.location)
    reports = runtestprotocol(item, nextitem=nextitem, log=False)
    if any(r.failed for r in reports):
        import warnings
        warnings.warn(f"loadflaky rerun: {item.nodeid} failed once, "
                      "retrying on a fresh cluster")
        try:
            # finalize EVERY live fixture so the retry boots clean
            item.session._setupstate.teardown_exact(None)
        except Exception:
            pass
        item._initrequest()
        reports = runtestprotocol(item, nextitem=nextitem, log=False)
    for report in reports:
        item.ihook.pytest_runtest_logreport(report=report)
    item.ihook.pytest_runtest_logfinish(nodeid=item.nodeid,
                                        location=item.location)
    return True
