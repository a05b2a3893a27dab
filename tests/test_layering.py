"""The import-layering contract (scripts/check_layering.py) as a test.

Holds the layer order (kernels -> core/engine -> core/sessions -> core
-> serving, no back-edges) and the LM fence: nothing in layers 0-4, in
``telemetry`` or in ``workload`` reaches the model zoo, its
configurations, the serve engine, the KV cache or the attention kernels.
"""

import importlib.util
import pathlib

SCRIPT = pathlib.Path(__file__).resolve().parents[1] / "scripts" / "check_layering.py"


def test_layering_contract_holds():
    spec = importlib.util.spec_from_file_location("check_layering", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main() == 0
