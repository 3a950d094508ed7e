"""Multi-host launcher — the TPU-native replacement for torchrun + the
SLURM/Cobalt ssh fan-out scripts (reference scripts/run_pretraining.sbatch:49-94,
run_pretraining.cobalt:46-91).

On a TPU pod there is one process per host; `jax.distributed.initialize`
performs the rendezvous (the c10d analog of sbatch:64-70), after which
`jax.devices()` spans the whole pod and a single SPMD program runs everywhere.
Coordinator discovery mirrors the reference's node-file inference: explicit
flags > environment (SLURM/COBALT nodefiles) > single-host default.
"""

from __future__ import annotations

import os
import subprocess
import sys
from typing import Optional

import jax

_INITIALIZED = False


def infer_coordinator(port: int = 9731) -> Optional[str]:
    """Infer the coordinator address the way the reference's sbatch infers the
    master node from $SLURM_NODELIST / $COBALT_NODEFILE (sbatch:49-62)."""
    nodelist = os.environ.get("SLURM_NODELIST")
    if nodelist:
        out = subprocess.run(
            ["scontrol", "show", "hostnames", nodelist],
            capture_output=True,
            text=True,
            check=False,
        )
        if out.returncode == 0 and out.stdout.strip():
            return f"{out.stdout.splitlines()[0].strip()}:{port}"
    nodefile = os.environ.get("COBALT_NODEFILE")
    if nodefile and os.path.exists(nodefile):
        with open(nodefile) as f:
            first = f.readline().strip()
        if first:
            return f"{first}:{port}"
    return None


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Join the pod-wide rendezvous. Safe to call on single-host runs (no-op
    when no multi-host environment is detected).

    On Cloud TPU VMs `jax.distributed.initialize()` auto-discovers everything;
    the explicit arguments cover SLURM-style clusters (the reference's target,
    sbatch:64-70).

    A rendezvous blocks until every process of the job has joined, so the
    decision to join is never taken quietly: the reason is printed before the
    call, and whatever the call raises propagates — a rank that cannot join
    must not go on to train alone against peers that wait for it.
    """
    global _INITIALIZED
    if _INITIALIZED:
        return
    reasons = []
    if (coordinator_address is not None or num_processes is not None
            or process_id is not None):
        reasons.append("explicit arguments")
    # Generic env override (the Cobalt ssh fan-out script sets these,
    # scripts/run_pretraining.cobalt; any launcher without SLURM vars can).
    # ANY of the three present marks the run as explicitly multi-host, so a
    # partially-configured rank fails loudly inside initialize() instead of
    # silently training solo while its peers block on the rendezvous.
    env_set = [v for v in ("JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES",
                           "JAX_PROCESS_ID") if v in os.environ]
    if env_set:
        reasons.append(",".join(env_set))
    # A TPU VM always carries TPU_WORKER_HOSTNAMES; only MORE THAN ONE
    # distinct host makes it a pod slice. One host — however many chips it
    # holds — is one process with nothing to rendezvous with.
    hosts = {h.strip() for h in
             os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",")} - {""}
    if len(hosts) > 1:
        reasons.append(f"TPU_WORKER_HOSTNAMES lists {len(hosts)} hosts")
    if "MEGASCALE_COORDINATOR_ADDRESS" in os.environ:
        reasons.append("MEGASCALE_COORDINATOR_ADDRESS")
    slurm = ("SLURM_NODELIST" in os.environ
             and int(os.environ.get("SLURM_NNODES", "1")) > 1)
    if slurm:
        reasons.append(f"SLURM_NNODES={os.environ['SLURM_NNODES']}")
    if not reasons:
        return  # single host, single process: nothing to rendezvous
    if coordinator_address is None:
        coordinator_address = os.environ.get("JAX_COORDINATOR_ADDRESS")
    if num_processes is None and "JAX_NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["JAX_NUM_PROCESSES"])
    if process_id is None and "JAX_PROCESS_ID" in os.environ:
        process_id = int(os.environ["JAX_PROCESS_ID"])
    kwargs = {}
    if coordinator_address or slurm or process_id is not None:
        kwargs["coordinator_address"] = coordinator_address or infer_coordinator()
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    elif slurm:
        kwargs["num_processes"] = int(os.environ["SLURM_NNODES"])
    if process_id is not None:
        kwargs["process_id"] = process_id
    elif slurm:
        kwargs["process_id"] = int(os.environ.get("SLURM_NODEID", "0"))
    print(f"launcher: joining a multi-host rendezvous ({'; '.join(reasons)}) "
          "— this blocks until every process of the job has started",
          file=sys.stderr, flush=True)
    jax.distributed.initialize(**kwargs)
    _INITIALIZED = True
