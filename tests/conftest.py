"""Test harness: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's @distributed_test strategy (tests/unit/common.py) —
multi-"chip" is simulated on one host. Env must be set before jax imports.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# Tests run on the virtual CPU mesh, whatever the environment says.
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture
def tmp_config_file(tmp_path):
    """Dump a config dict to a json file, return the path
    (mirrors reference args_from_dict)."""
    import json

    def _write(config_dict, name="ds_config.json"):
        path = tmp_path / name
        path.write_text(json.dumps(config_dict))
        return str(path)

    return _write
