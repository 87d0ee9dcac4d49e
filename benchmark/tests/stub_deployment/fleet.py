"""Part ``fleet``: today's fleet, with a column of this deployment's own."""

from benchmark.gen import fleet as default


def seed_fleet(server, config: dict) -> dict:
    table = default.seed_fleet(server, config)
    table["zone"] = table["rack"] % 2
    return table
