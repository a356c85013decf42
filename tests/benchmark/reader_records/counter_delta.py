"""A run that holds what a `counter_delta` reader looks for, and the value it must read."""
def record(reader: dict) -> tuple:
    counters = {name: i + 1 for i, name in enumerate(reader["counters"])}
    return dict(counters=dict(counters, **{"some.other.counter": 1000})), \
        float(sum(counters.values()))
