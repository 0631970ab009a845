"""Test configuration: an 8-device virtual CPU mesh.

Multi-chip sharding is checked on XLA's host platform with 8 virtual
devices. The platform itself comes from the test line's environment
(``JAX_PLATFORMS=cpu``, see ROADMAP.md "Tier-1 verify"); this file only
asks that platform for 8 devices, before any test imports JAX.
"""

from adlb_tpu.utils.jaxenv import virtual_cpu_devices

virtual_cpu_devices(8)

# hang diagnosis lives in pytest.ini (faulthandler_timeout): pytest's
# built-in plugin dumps to the ORIGINAL stderr fd, surviving --capture,
# and covers setup/teardown phases a fixture-armed timer would miss
