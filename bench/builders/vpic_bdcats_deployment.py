"""h5bench VPIC-IO checkpoint writers beside BDCATS-IO readers at a
deployment's shape: the configuration's ``n_clients``, ``n_osts`` and
``stripe_count``.  One scenario, named after the configuration."""


def program(config: dict) -> dict:
    from repro.lab.scenarios import vpic_bdcats_deployment

    return {config["name"]: vpic_bdcats_deployment(
        config["n_clients"], config["n_osts"], config["stripe_count"])}


def reference(config: dict) -> dict:
    import reference as ref

    return {config["name"]: ref.vpic_bdcats_deployment(
        config["n_clients"], config["n_osts"], config["stripe_count"])}
