"""Shared example bootstrap: run on an 8-device virtual CPU mesh so every
example works on any machine (swap for real TPU devices in production —
nothing else changes)."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
