"""``ShardedStore`` over the cell's chips, one shard per chip, owner
routed by source hash; capacities by the smoke's rule
(``ustream.capacity``) per shard."""


def store_kwargs(cfg: dict) -> dict:
    from ustream import capacity, pow2_at_least
    n = cfg["chips"]
    c = capacity(cfg, n_shards=n)
    cap = cfg["capacity"]
    # a shard's vertex table holds its own sources AND every destination
    # its edges name: on a uniform graph, nearly every vertex
    return dict(c["common"], n_shards=n, n_per_shard=c["n_rows"],
                expected_n=c["V"], batch=cap["batch"] * n,
                query_batch=cap["query_batch"] * n,
                m_cap=pow2_at_least(c["entries"] + c["entries"] // 4))


def make_store(api, jax, cfg: dict, chips: int, kwargs: dict):
    return api.make_store("sharded", devices=jax.devices()[:chips], **kwargs)
