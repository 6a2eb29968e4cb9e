"""The client a cell drives, chosen by its configuration file alone.

Without a ``client`` key the cell runs on
``connect("service", StratumConfig.make())``.  With

    "client": {"target": "fabric", "n_shards": 4}

it runs on the in-process fabric, ``connect("fabric",
StratumConfig.make(n_shards=4))``, one shard per chip: the cell's agents
are split into ``n_shards`` equal blocks, and block ``s`` (agent ``i`` is
in block ``⌊i · n_shards / agents⌋``) is pinned to shard ``s`` through
``SubmitOptions(affinity=...)``.  The fabric would otherwise route by the
batch's sources, and every job of a cell reads the same table, so every
job would land on one shard."""

from __future__ import annotations

FABRIC_KEYS = {"target", "n_shards"}


def shards(config: dict, chips: int, agents: int) -> int:
    """The cell's shard count, 0 for the service.  A fabric's
    ``n_shards`` has to equal the cell's chips and divide its agents
    (``ValueError`` otherwise); nothing here touches JAX."""
    client = config.get("client")
    if client is None:
        return 0
    if not isinstance(client, dict) or set(client) != FABRIC_KEYS \
            or client["target"] != "fabric":
        raise ValueError(f"'client' is {client!r}; the only client a "
                         f"configuration names is "
                         f"{{\"target\": \"fabric\", \"n_shards\": n}}")
    n = client["n_shards"]
    if type(n) is not int or n < 1:
        raise ValueError(f"n_shards is {n!r}, not a whole number >= 1")
    if n != chips:
        raise ValueError(f"the configuration's fabric has {n} shards and "
                         f"the cell {chips} chips: one shard runs on each "
                         f"chip")
    if agents % n:
        raise ValueError(f"{agents} agents do not split evenly over {n} "
                         f"shards")
    return n


def connect(n_shards: int):
    from repro.client import StratumConfig, connect as _connect
    if not n_shards:
        return _connect("service", StratumConfig.make())
    return _connect("fabric", StratumConfig.make(n_shards=n_shards))


def affinity_keys(shard_ids: list, vnodes: int) -> dict:
    """One routing key per shard, ``{shard_id: key}``, found by probing a
    replica of the fabric's consistent-hash ring."""
    from repro.service.fabric.ring import ConsistentHashRing
    ring = ConsistentHashRing(shard_ids, vnodes=vnodes)
    keys: dict = {}
    k = 0
    while len(keys) < len(shard_ids):
        keys.setdefault(ring.route(f"bench-{k}"), f"bench-{k}")
        k += 1
    return keys


def pins(client, n_shards: int) -> list:
    """``[(shard_id, routing key)]`` in shard order, shard ``s`` on chip
    ``s``; ``[None]`` for the service, which has one place to run."""
    if not n_shards:
        return [None]
    ids = sorted(client.fabric.shard_ids(),
                 key=lambda s: int(s.rsplit("-", 1)[1]))
    keys = affinity_keys(ids, client.config.service.vnodes)
    return [(sid, keys[sid]) for sid in ids]
