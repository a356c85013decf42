"""A run that holds what a `harness_value` reader looks for, and the value it must read."""
def record(reader: dict) -> tuple:
    return dict(values={reader["key"]: 7, "another": 9}), 7.0
