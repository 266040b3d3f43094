"""Per-device replica placement on four virtual CPU devices: replica i
of the fabric keeps its params, adapter, optimizer state, KV pool and
tenant slots on device i; serving over four devices gives the same
greedy tokens as one replica on device 0; and federated rounds that
average adapters across devices keep every replica on its own device.

Runs in a subprocess: the four-device host platform must be configured
before JAX starts, and on a TPU host the CPU has to be forced."""
import os
import subprocess
import sys

SCRIPT = r"""
import jax
from repro.launch.serve import (run_combined_fabric_serving,
                                run_multi_replica_serving)

devs = jax.devices()
assert len(devs) == 4, devs
trace = dict(smoke=True, n_requests=8, prompt_len=16, gen_tokens=8,
             batch_size=2, paged=True, block_size=8, n_adapters=2,
             seed=0, verbose=False)
one = run_multi_replica_serving("qwen1.5-0.5b", n_replicas=1, **trace)
four = run_multi_replica_serving("qwen1.5-0.5b", n_replicas=4, **trace)
want = {f"r{i}": [devs[i].id] for i in range(4)}
assert one["devices"] == {"r0": [devs[0].id]}, one["devices"]
assert four["devices"] == want, four["devices"]
for out in (one, four):
    assert out["completed"] == 8, out["completed"]
    assert out["fault_tolerance"]["failovers"] == 0
assert four["outputs"] == one["outputs"], (four["outputs"], one["outputs"])

co = run_combined_fabric_serving("qwen1.5-0.5b", n_replicas=4, rounds=1,
                                 steps_per_round=2, **trace)
assert co["completed"] == 8 and co["fl_rounds"] >= 1, co["fl_rounds"]
assert co["fault_tolerance"]["failovers"] == 0
assert co["devices"] == want, co["devices"]
print("OK")
"""


def test_replicas_live_on_their_own_devices():
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", SCRIPT],
                         capture_output=True, text=True, timeout=600,
                         env=env)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "OK" in res.stdout
