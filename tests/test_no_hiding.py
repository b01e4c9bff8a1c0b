"""Nothing on the device path may turn a device or compile failure into
a quiet CPU run: each former swallow gets the failure injected and must
raise it (ISSUE 23 step 2)."""

import asyncio
import json

import jax
import numpy as np
import pytest

from openr_tpu import main as daemon_main
from openr_tpu.decision.decision import make_solver
from openr_tpu.decision.tpu_solver import TpuSpfSolver
from openr_tpu.ops.xla_cache import instrument_jit, ledger
from openr_tpu.runtime.lifecycle import boot_tracer


@pytest.fixture
def no_device(monkeypatch):
    def devices(*a, **kw):
        raise RuntimeError("Unable to initialize backend 'tpu': injected")

    monkeypatch.setattr(jax, "devices", devices)


@pytest.mark.parametrize("backend", ["tpu", "auto"])
def test_make_solver_raises_when_the_backend_cannot_initialize(
    no_device, backend
):
    with pytest.raises(RuntimeError, match="injected"):
        make_solver("node-1", backend, small_graph_nodes=2816)


@pytest.mark.parametrize("backend", ["tpu", "auto"])
def test_make_solver_device_backends_are_the_device_solver(backend):
    solver = make_solver("node-1", backend, small_graph_nodes=2816)
    assert isinstance(solver, TpuSpfSolver)
    assert solver.small_graph_nodes == (2816 if backend == "auto" else 0)


def test_device_init_failure_fails_the_boot(no_device, tmp_path):
    cfg = tmp_path / "n.conf"
    cfg.write_text(json.dumps({
        "node_name": "boot-fail",
        "decision_config": {"solver_backend": "tpu"},
    }))
    args = daemon_main.parse_args(["--config", str(cfg)])
    try:
        with pytest.raises(RuntimeError, match="injected"):
            asyncio.run(daemon_main.run_daemon(args))
        # the phase is on the record, without a device identity
        [ph] = [
            p for p in boot_tracer.report()["phases"]
            if p["name"] == "device_init"
        ]
        assert "platform" not in json.dumps(ph)
    finally:
        boot_tracer.reset()


def test_device_identity_names_the_device():
    ident = daemon_main.device_identity()
    dev = jax.devices()[0]
    assert ident == {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "devices": len(jax.devices()),
    }


def test_instrument_jit_surfaces_a_refused_compile():
    class Refusing:
        """A jitted callable whose compile the backend refuses."""

        def lower(self, *args, **kwargs):
            return self

        def compile(self):
            raise RuntimeError("Mosaic failed to compile TPU kernel: injected")

        def __call__(self, *args, **kwargs):  # the old silent fallback
            raise AssertionError("fell back to the plain jitted fn")

    fn = instrument_jit("refused-kern", Refusing())
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        fn(np.arange(4))
    assert "refused-kern" not in ledger.snapshot()
    assert not fn.is_installed()
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        fn.prime(jax.ShapeDtypeStruct((4,), np.int32))
