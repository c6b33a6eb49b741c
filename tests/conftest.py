"""Test config.  IMPORTANT: never set xla_force_host_platform_device_count
here — smoke tests must see 1 device; multi-device tests spawn subprocesses
(tests/test_distributed.py)."""
from hypothesis import HealthCheck, settings

settings.register_profile(
    "ci", deadline=None,
    suppress_health_check=[HealthCheck.too_slow,
                           HealthCheck.data_too_large])
settings.load_profile("ci")


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running integration test")
