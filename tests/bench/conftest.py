import json
import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")


@pytest.fixture
def small_cell():
    """A cell built from a small configuration and mix of ``data/``."""
    def make(config, mix, chips=1):
        return {"name": f"{config}-{mix}", "chips": chips,
                "config": json.loads((DATA / f"{config}.json").read_text()),
                "mix": json.loads((DATA / f"{mix}.json").read_text()),
                "end_to_end": [], "per_layer": []}
    return make


@pytest.fixture
def cpu_device():
    """Stands in for the benchmark's look for a chip."""
    def require(chips):
        import jax
        d = jax.devices()[0]
        return {"platform": d.platform, "kind": d.device_kind,
                "count": len(jax.devices())}
    return require
