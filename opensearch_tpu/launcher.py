"""Node launcher: config loading, bootstrap checks, process lifecycle.

Re-design of the reference's distribution entry path —
bootstrap/Bootstrap.java:360 (environment setup, bootstrap checks, node
start, shutdown hook) + OpenSearch.java (CLI: config path and -E setting
overrides) + BootstrapChecks.java (dev mode warns, production mode —
binding a non-loopback address — hard-fails). `python -m opensearch_tpu`
is the bin/opensearch analog:

    python -m opensearch_tpu --config /etc/opensearch_tpu/opensearch.yml \
        -E node.name=n1 -E http.port=9200

Config is the reference's opensearch.yml (flat-keyed YAML). A node with
`discovery.seed_hosts` or `cluster.initial_cluster_manager_nodes` starts
the full ClusterNode (transport + coordination); otherwise a single
in-process Node serves HTTP directly.
"""

from __future__ import annotations

import argparse
import os
import signal
import sys
import threading
import time
from typing import Dict, Optional, Tuple


def load_config(path: Optional[str]) -> Dict:
    """opensearch.yml → flat settings dict. Nested YAML maps flatten to
    dotted keys (the reference accepts both shapes)."""
    if not path or not os.path.exists(path):
        return {}
    import yaml
    with open(path) as f:
        raw = yaml.safe_load(f) or {}

    flat: Dict = {}

    def flatten(prefix: str, value):
        if isinstance(value, dict):
            for k, v in value.items():
                flatten(f"{prefix}.{k}" if prefix else str(k), v)
        else:
            flat[prefix] = value

    flatten("", raw)
    return flat


def apply_overrides(settings: Dict, overrides) -> Dict:
    """-E key=value CLI overrides (highest precedence, like the ref)."""
    out = dict(settings)
    for item in overrides or []:
        key, sep, value = item.partition("=")
        if not sep:
            raise SystemExit(f"-E expects key=value, got [{item}]")
        out[key.strip()] = value.strip()
    return out


def bootstrap_checks(settings: Dict) -> list:
    """BootstrapChecks.java: a list of (name, ok, detail). The caller
    (main) aborts on failures in production mode — a non-loopback bind —
    and logs them as warnings in dev mode."""
    checks = []

    data_path = settings.get("path.data")
    if data_path:
        ok = True
        detail = data_path
        try:
            os.makedirs(data_path, exist_ok=True)
            probe = os.path.join(data_path, ".writable")
            with open(probe, "w") as f:
                f.write("ok")
            os.remove(probe)
        except OSError as e:
            ok, detail = False, f"{data_path}: {e}"
        checks.append(("data path is writable", ok, detail))

    try:
        import resource
        soft, _ = resource.getrlimit(resource.RLIMIT_NOFILE)
        checks.append(("max file descriptors >= 4096",
                       soft == resource.RLIM_INFINITY or soft >= 4096,
                       str(soft)))
    except (ImportError, ValueError):
        pass

    # the device the node will serve from, said once and loudly: a node
    # that came up on CPU reads `platform=cpu` in its start-up log
    try:
        import jax
        dev = jax.devices()[0]
        checks.append(("jax device present", True,
                       f"jax {jax.__version__} platform={dev.platform} "
                       f"device_kind={dev.device_kind} "
                       f"devices={jax.device_count()}"))
    except (ImportError, RuntimeError) as e:
        checks.append(("jax device present", False, str(e)))
    return checks


# special host aliases (reference NetworkService special values)
_HOST_ALIASES = {"_local_": "127.0.0.1", "_site_": "0.0.0.0",
                 "_global_": "0.0.0.0"}


def resolve_host(value) -> str:
    return _HOST_ALIASES.get(str(value), str(value))


def is_production(settings: Dict) -> bool:
    host = resolve_host(settings.get("http.host",
                                     settings.get("network.host",
                                                  "127.0.0.1")))
    return host not in ("127.0.0.1", "localhost", "::1")


def start_node(settings: Dict, config_dir: Optional[str] = None):
    """Build and start the node per settings; returns (node, http_server)."""
    from opensearch_tpu.rest.http import HttpServer

    node_name = str(settings.get("node.name") or f"node-{os.getpid()}")
    http_host = resolve_host(settings.get("http.host",
                                          settings.get("network.host",
                                                       "127.0.0.1")))
    http_port = int(settings.get("http.port", 9200))
    data_path = settings.get("path.data")

    seed_hosts = settings.get("discovery.seed_hosts")
    initial = settings.get("cluster.initial_cluster_manager_nodes") or []
    if isinstance(initial, str):
        initial = [n.strip() for n in initial.split(",") if n.strip()]

    if seed_hosts or initial:
        node = _start_cluster_node(settings, node_name, initial, config_dir)
    else:
        from opensearch_tpu.node import Node
        node = Node(node_name=node_name, settings=settings,
                    data_path=data_path)

    from opensearch_tpu.transport.security import SecurityConfig
    security = SecurityConfig(settings)
    server = HttpServer(node, host=http_host, port=http_port,
                        security=security)
    server.start()
    return node, server


def _start_cluster_node(settings: Dict, node_name: str, initial: list,
                        config_dir: Optional[str]):
    """Cluster mode: bootstrap a new cluster when this node is named in
    cluster.initial_cluster_manager_nodes (resolving co-founders through
    the seed list), else discover + join via seed hosts."""
    from opensearch_tpu.cluster.discovery import (discover_and_join,
                                                  seed_addresses)
    from opensearch_tpu.cluster.service import ClusterNode

    transport_host = resolve_host(settings.get(
        "transport.host", settings.get("network.host", "127.0.0.1")))
    transport_port = int(settings.get("transport.port", 0) or 0)
    node = ClusterNode(node_name, host=transport_host, port=transport_port,
                       settings=settings)

    if node_name in initial:
        peers: Dict[str, Tuple[str, int]] = {node_name: node.address}
        others = [n for n in initial if n != node_name]
        deadline = time.time() + 60.0
        while others and time.time() < deadline:
            for host, port in seed_addresses(settings, config_dir):
                peer_id = node.transport.probe_address(host, port,
                                                       timeout=2.0)
                if peer_id in others:
                    peers[peer_id] = (host, port)
                    others.remove(peer_id)
            if others:
                time.sleep(0.5)
        if others:
            node.close()
            raise SystemExit(
                f"could not resolve initial cluster manager nodes {others} "
                f"through discovery.seed_hosts")
        node.bootstrap(peers)
    else:
        join_timeout = float(settings.get("discovery.join_timeout", 60.0))
        joined = discover_and_join(node, settings, config_dir,
                                   timeout=join_timeout)
        if joined is None:
            node.close()
            raise SystemExit(
                "no seed host answered; cannot join a cluster "
                "(set cluster.initial_cluster_manager_nodes to form one)")
    return node


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="opensearch_tpu",
        description="Start an opensearch_tpu node (bin/opensearch analog)")
    parser.add_argument("-c", "--config", default=None,
                        help="path to opensearch.yml")
    parser.add_argument("-E", action="append", dest="overrides",
                        metavar="key=value",
                        help="setting override (repeatable)")
    args = parser.parse_args(argv)

    settings = apply_overrides(load_config(args.config), args.overrides)
    config_dir = os.path.dirname(os.path.abspath(args.config)) \
        if args.config else None

    from opensearch_tpu.common.logging import configure_logging, get_logger
    configure_logging(settings)
    log = get_logger("bootstrap")

    production = is_production(settings)
    failures = []
    for name, ok, detail in bootstrap_checks(settings):
        if ok:
            log.info(f"bootstrap check [{name}]: ok ({detail})")
        else:
            # failures must survive a raised logger.level — the operator
            # needs to see WHICH check failed when startup aborts
            log.error(f"bootstrap check [{name}]: FAILED ({detail})")
            failures.append(name)
    if failures and production:
        log.error("bootstrap checks failed in production mode; aborting")
        return 78

    node, server = start_node(settings, config_dir)
    name = getattr(node, "node_name", getattr(node, "node_id", "?"))
    print(f"[{name}] started: http on {server.host}:{server.port}"
          + (f", transport on {node.address[0]}:{node.address[1]}"
             if hasattr(node, "address") else ""),
          flush=True)

    stop = threading.Event()

    def on_signal(signum, frame):
        stop.set()

    signal.signal(signal.SIGTERM, on_signal)
    signal.signal(signal.SIGINT, on_signal)
    stop.wait()
    print(f"[{name}] stopping", flush=True)
    if hasattr(node, "close"):
        node.close()
    return 0
