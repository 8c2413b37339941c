"""Seeded deployments for the benchmark: the inputs and, beside each, the
plain model the checker holds the answer to.

The generators are copies of the program's own (`chaos/runner.make_flow`,
`chip_smoke.build_cp`, `lower/fleetgen.generate_fleet_kdl` /
`generate_servers_kdl`), kept here so that a later PR cannot change what a
cell is made of. Every generator returns the input the program is given
(a `Flow`, or KDL text) AND a model in plain dicts — names, demands, ports,
volumes, anti-affinity, capacities — written down at the moment the input
is drawn, never read back from what the program parsed or lowered.

Model format (what `checker.Model` takes):
    services: [{"name", "replicas", "cpu", "memory", "disk",
                "ports": [int], "volumes": [str], "anti_affinity": [name],
                "eligible": None | [server name]}]
    servers:  {name: {"cpu", "memory", "disk"}}
A service with replicas > 1 is rows "name#0".."name#k-1"; one replica is the
row "name" (the program's documented row naming, lower/tensors.py).
"""

from __future__ import annotations

import os
import random

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# lower/tensors.py SYNTH_*_RANGE at the time of the copy
CPU_RANGE = (0.05, 0.5)
MEM_RANGE = (32.0, 512.0)
DISK_RANGE = (0.0, 1024.0)


def node_slug(i: int) -> str:
    return f"node{i:03d}"


def live_stage(services: int, nodes: int, seed: int, stage: str = "app0"):
    """One stage shaped like a production fleet (chaos/runner.make_flow):
    dependency chains of depth <= 5, mixed demand, every 20th service with
    2 replicas and hard self-anti-affinity; `nodes` servers sized for ~2x
    the stage's demand (chip_smoke.build_cp). Returns (flow, model)."""
    from fleetflow_tpu.core.model import Flow, ResourceSpec, Service, Stage

    rng = random.Random(seed)
    flow = Flow(name="chaosfleet")
    names = [f"svc{i:04d}" for i in range(services)]
    model_services = []
    for i, name in enumerate(names):
        cpu = rng.choice((0.05, 0.1, 0.2))
        mem = float(rng.choice((32, 64, 128)))
        svc = Service(name=name, image="chaos-app", version="1",
                      resources=ResourceSpec(cpu=cpu, memory=mem, disk=0.0))
        if i % 5 != 0:
            svc.depends_on = [names[i - 1]]
        spec = {"name": name, "replicas": 1, "cpu": cpu, "memory": mem,
                "disk": 0.0, "ports": [], "volumes": [],
                "anti_affinity": [], "eligible": None}
        if i % 20 == 10:
            svc.replicas = 2
            svc.anti_affinity = [name]
            spec["replicas"] = 2
            spec["anti_affinity"] = [name]
        flow.services[name] = svc
        model_services.append(spec)
    slugs = [node_slug(i) for i in range(nodes)]
    flow.stages[stage] = Stage(name=stage, services=names,
                               servers=list(slugs))
    cpu_cap = max(2.0 * (0.15 * services + 100.0) / nodes, 1.0)
    servers = {s: {"cpu": cpu_cap, "memory": cpu_cap * 2048.0,
                   "disk": 10240.0} for s in slugs}
    return flow, {"services": model_services, "servers": servers}


def fleet_kdl(fleet: str, n_services: int, *, seed: int, n_nodes_hint: int,
              port_base: int, port_fraction: float = 0.2,
              volume_fraction: float = 0.1, dep_depth_max: int = 5,
              replica_fraction: float = 0.05, coloc_fraction: float = 0.05):
    """KDL text of one tenant fleet with a stage "prod"
    (lower/fleetgen.generate_fleet_kdl, same draws in the same order) and
    its model services. Colocation is a soft preference in the program and
    is left out of the model."""
    rng = np.random.default_rng(seed)
    names = [f"{fleet}-svc-{i:05d}" for i in range(n_services)]
    n_ports = max(int(n_services * port_fraction / 4), 1)
    port_members = np.zeros(n_ports, dtype=np.int64)
    n_vols = max(int(n_services * volume_fraction / 3), 1)

    dep_of: dict[int, int] = {}
    order = rng.permutation(n_services)
    i = 0
    while i < len(order):
        chain_len = int(rng.integers(1, dep_depth_max + 1))
        chain = order[i:i + chain_len]
        for a, b in zip(chain[1:], chain[:-1]):
            dep_of[int(a)] = int(b)
        i += chain_len

    lines: list[str] = [f'project "{fleet}"', ""]
    specs = []
    for s, name in enumerate(names):
        cpu = rng.uniform(*CPU_RANGE)
        mem = rng.uniform(*MEM_RANGE)
        disk = rng.uniform(*DISK_RANGE)
        # the model holds the numbers as the KDL text states them
        spec = {"name": name, "replicas": 1, "cpu": float(f"{cpu:.3f}"),
                "memory": float(f"{mem:.1f}"), "disk": float(f"{disk:.1f}"),
                "ports": [], "volumes": [], "anti_affinity": [],
                "eligible": None}
        lines.append(f'service "{name}" {{')
        lines.append(f'    image "registry.example/{fleet}/app:1.0"')
        lines.append('    resources {')
        lines.append(f'        cpu {cpu:.3f}')
        lines.append(f'        memory {mem:.1f}')
        lines.append(f'        disk {disk:.1f}')
        lines.append('    }')
        if s in dep_of:
            lines.append(f'    depends_on "{names[dep_of[s]]}"')
        if rng.random() < port_fraction:
            open_ids = np.flatnonzero(port_members < n_nodes_hint - 1)
            if open_ids.size:
                p = int(open_ids[int(rng.integers(0, open_ids.size))])
                port_members[p] += 1
                lines.append(f'    port host={port_base + p} container=8080')
                spec["ports"].append(port_base + p)
        if rng.random() < volume_fraction:
            v = int(rng.integers(0, n_vols))
            lines.append(
                f'    volume "/data/{fleet}/vol-{v:04d}" "/var/data"')
            spec["volumes"].append(f"/data/{fleet}/vol-{v:04d}")
        if not spec["ports"] and rng.random() < replica_fraction:
            spec["replicas"] = int(rng.integers(2, 4))
            lines.append(f'    replicas {spec["replicas"]}')
        if s in dep_of and rng.random() < coloc_fraction:
            lines.append(f'    colocate_with "{names[dep_of[s]]}"')
        lines.append('}')
        specs.append(spec)
    lines.append("")
    lines.append('stage "prod" {')
    lines.append('    placement "spread_across_pool"')
    for name in names:
        lines.append(f'    service "{name}"')
    lines.append('}')
    return "\n".join(lines) + "\n", specs


def servers_kdl(n_nodes: int, *, seed: int, cpu: float = 8.0,
                memory_mb: float = 8192.0, disk_mb: float = 32768.0):
    """KDL text of the registry's shared server pool
    (lower/fleetgen.generate_servers_kdl) and its model servers."""
    rng = np.random.default_rng(seed)
    lines: list[str] = []
    servers = {}
    for j in range(n_nodes):
        jitter = rng.uniform(1.0, 1.25)
        name = f"node-{j:04d}"
        servers[name] = {"cpu": float(f"{cpu * jitter:.2f}"),
                         "memory": float(f"{memory_mb * jitter:.0f}"),
                         "disk": float(f"{disk_mb * jitter:.0f}")}
        lines.append(f'server "{name}" {{')
        lines.append('    capacity {')
        lines.append(f'        cpu {cpu * jitter:.2f}')
        lines.append(f'        memory {memory_mb * jitter:.0f}')
        lines.append(f'        disk {disk_mb * jitter:.0f}')
        lines.append('    }')
        lines.append('}')
    return "\n".join(lines) + "\n", servers


def registry(fleets: int, services_per_fleet: int, nodes: int, seed: int):
    """`fleets` tenant fleets over one pool (chip_smoke.gen_registry).
    Returns ({fleet: kdl text}, servers kdl text, model). Model rows carry
    the registry's namespacing, "<fleet>.prod.<service>"."""
    texts, services = {}, []
    for i in range(fleets):
        fleet = f"t{i}"
        # disjoint port_base per fleet: a port's conflict identity spans
        # fleets, and a merged group could outgrow the pool
        text, specs = fleet_kdl(fleet, services_per_fleet,
                                seed=seed + 100 + i, n_nodes_hint=nodes,
                                port_base=10000 + i * services_per_fleet)
        texts[fleet] = text
        for spec in specs:
            services.append(dict(spec, name=f"{fleet}.prod.{spec['name']}"))
    pool_text, servers = servers_kdl(nodes, seed=seed + 7)
    return texts, pool_text, {"services": services, "servers": servers}


def deployment(config: dict, seed: int, rehearsal: bool):
    """The configuration's one live stage: (flow, stage name, model).
    `kind: generated` draws it from the seed; `kind: kdl` parses the KDL
    file beside the configuration and takes the model the file states."""
    dep = dict(config["deployment"])
    if rehearsal:
        dep.update(config.get("rehearsal", {}).get("deployment", {}))
    if dep["kind"] == "generated":
        flow, model = live_stage(dep["services"], dep["nodes"], seed,
                                 stage=dep["stage"])
        return flow, dep["stage"], model
    if dep["kind"] == "kdl":
        from fleetflow_tpu.core.parser import parse_kdl_string
        with open(os.path.join(HERE, "configs", dep["file"]),
                  encoding="utf-8") as f:
            text = f.read()
        for key, value in dep.get("variables", {}).items():
            text = text.replace("{{ %s }}" % key, value)
        return parse_kdl_string(text), dep["stage"], dep["model"]
    raise ValueError(f"unknown deployment kind {dep['kind']!r}")
