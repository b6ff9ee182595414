"""Arrivals ``evenly_spaced``: tx i is due ``i / rate_tps`` after the
schedule's start, whatever the seed (an open loop, as ``tm-load-test -r``
paces). No parameters."""


def offsets_ns(n_txs: int, rate_tps: float, seed: int, params: dict) -> list[int]:
    period_ns = 1e9 / rate_tps
    return [int(i * period_ns) for i in range(n_txs)]
