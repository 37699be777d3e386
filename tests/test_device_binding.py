"""Runtime devices bound to real JAX devices, checked on the CPU.

A child process gets four CPU devices
(``--xla_force_host_platform_device_count=4``, ``JAX_PLATFORMS=cpu``)
and reports where co-execution devices, kernel launches and serving-mesh
replicas landed; the tests below read that report.  In this process
(one CPU device) the same calls share the one device, and the Pallas
target runs in interpret mode because the backend is the CPU.
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.backend import pallas_interpret
from repro.core import BuildError, KernelBuilder
from repro.runtime import Context
from repro.runtime.platform import Platform

SRC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")

CHILD = r"""
import json
import numpy as np
import jax
from repro import configs
from repro.core import KernelBuilder
from repro.distributed.sharding import BASELINE_RULES
from repro.models import init_params
from repro.runtime import Context
from repro.runtime.platform import Platform
from repro.serving import Request, ServingMesh


def build():
    b = KernelBuilder("bind_scale")
    x = b.arg_buffer("x", "float32")
    g = b.global_id(0)
    x[g] = x[g] * 2.0 + 1.0
    return b.finish()


def where(tree):
    return sorted({str(d) for leaf in jax.tree.leaves(tree)
                   for d in leaf.devices()})


plat = Platform()
devs = plat.co_devices(4)
rep = {"n_jax": len(jax.devices()),
       "bound": [str(d.jax_device) for d in devs],
       "mem": [d.info.global_mem_size for d in devs]}

ctx = Context(devices=devs, platform=plat)
kern = ctx.create_program(build).create_kernel()
x = np.arange(1024, dtype=np.float32)
kern.set_args(x=x)
rep["launch"] = [where(d.launch(kern.bind(d, (64,)), {"x": x}, (1024,)))
                 for d in devs]
single = ctx.launch(kern, (1024,), (64,), device=devs[0])
co = ctx.create_co_executor(devs).launch(kern, (1024,), (64,))
rep["co_bitwise"] = bool(co["x"].tobytes() == single["x"].tobytes()
                         and single["x"].tobytes()
                         == (x * 2 + 1).tobytes())

cfg = configs.get_smoke("smollm-135m")
params = init_params(cfg, jax.random.PRNGKey(0))
mesh = ServingMesh(cfg, params, BASELINE_RULES, n_replicas=4, platform=plat,
                   batch_slots=2, max_seq=32)
rng = np.random.default_rng(0)
for i in range(4):
    mesh.submit(Request(prompt=rng.integers(0, cfg.vocab, 6).astype(np.int32),
                        max_new_tokens=2), replica=i)
done = mesh.drain()
rep["mesh_done"] = sum(1 for r in done if r.done)
rep["mesh_bound"] = [str(r.device.jax_device) for r in mesh.replicas]
rep["mesh_params"] = [where(r.engine._exec.params) for r in mesh.replicas]
rep["mesh_state"] = [where(r.engine._state) for r in mesh.replicas]
print(json.dumps(rep))
"""


@pytest.fixture(scope="module")
def four_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH",
                                                            ""))
    res = subprocess.run([sys.executable, "-c", CHILD], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-4000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_co_devices_bind_distinct_jax_devices(four_cpu):
    assert four_cpu["n_jax"] == 4
    assert len(set(four_cpu["bound"])) == 4


def test_cpu_device_memory_size_defaults_to_1gib(four_cpu):
    # the CPU backend reports no bytes_limit
    assert four_cpu["mem"] == [1 << 30] * 4


def test_launch_output_lives_on_its_device(four_cpu):
    assert four_cpu["launch"] == [[b] for b in four_cpu["bound"]]


def test_coexec_over_four_devices_bitwise(four_cpu):
    assert four_cpu["co_bitwise"]


def test_mesh_replicas_on_distinct_devices(four_cpu):
    bound = four_cpu["mesh_bound"]
    assert len(set(bound)) == 4
    assert four_cpu["mesh_done"] == 4
    assert four_cpu["mesh_params"] == [[b] for b in bound]
    assert four_cpu["mesh_state"] == [[b] for b in bound]


def test_co_devices_share_a_single_device_round_robin():
    jdevs = jax.devices()
    devs = Platform().co_devices(len(jdevs) + 2)
    assert [d.jax_device for d in devs] == \
        [jdevs[i % len(jdevs)] for i in range(len(devs))]


def _suite_like():
    b = KernelBuilder("bind_gather")
    x = b.arg_buffer("x", "float32")
    y = b.arg_buffer("y", "float32")
    g = b.global_id(0)
    y[g] = x[g] + x[g]
    return b.finish()


def test_pallas_target_interprets_on_cpu():
    assert jax.default_backend() == "cpu" and pallas_interpret()
    ctx = Context()
    dev = ctx.platform.get_devices("pallas")[0]
    kern = ctx.create_program(_suite_like).create_kernel()
    x = np.arange(64, dtype=np.float32)
    kern.set_args(x=x, y=np.zeros(64, np.float32))
    assert kern.bind(dev, (16,)).prog.interpret is True
    out = ctx.launch(kern, (64,), (16,), device=dev)
    assert out["y"].tobytes() == (x + x).tobytes()


def test_pallas_target_refusal_is_typed_build_error(monkeypatch):
    """Where the backend compiler refuses a kernel (here: the CPU, asked
    for a compiled Pallas kernel), the launch raises BuildError naming
    the kernel, with the compiler's message in the build log."""
    from repro.core.targets import pallas_target
    monkeypatch.setattr(pallas_target, "pallas_interpret", lambda: False)
    ctx = Context()
    dev = ctx.platform.get_devices("pallas")[0]
    kern = ctx.create_program(_suite_like).create_kernel()
    kern.set_args(x=np.ones(64, np.float32), y=np.zeros(64, np.float32))
    with pytest.raises(BuildError) as ei:
        ctx.launch(kern, (64,), (16,), device=dev)
    assert "bind_gather" in str(ei.value)
    assert ei.value.build_log.strip()
    assert ei.value.code_name == "CL_BUILD_PROGRAM_FAILURE"
