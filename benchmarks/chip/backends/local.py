"""One chip through ``LocalStore``; capacities by the smoke's rule
(``ustream.capacity``)."""


def store_kwargs(cfg: dict) -> dict:
    from ustream import capacity
    c = capacity(cfg, n_shards=1)
    cap = cfg["capacity"]
    return dict(c["common"], n_max=c["n_rows"], expected_n=c["V"],
                batch=cap["batch"], m_cap=c["per_entry"] * c["E"],
                probe_width=cap["probe_width"], k_big=cap["k_big"])


def make_store(api, jax, cfg: dict, chips: int, kwargs: dict):
    return api.make_store("local", **kwargs)
