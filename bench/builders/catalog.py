"""Registered scenarios of the lab's catalog at their own sizes, by the
names in the configuration's ``scenarios``."""


def program(config: dict) -> dict:
    from repro.lab.scenarios import get_scenario

    return {n: get_scenario(n) for n in config["scenarios"]}


def reference(config: dict) -> dict:
    import reference as ref

    cat = ref.catalog()
    return {n: cat[n] for n in config["scenarios"]}
