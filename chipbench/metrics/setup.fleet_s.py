"""Host seconds to build the fleet: nodes, captains, Beacon registration
and the service's replicas (``ArmadaSystem``, ``Beacon.register_node``)."""


def read(ctx):
    return ctx.setup.get("fleet_s")
