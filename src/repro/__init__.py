"""GPFL reproduction: gradient-projection client selection at datacenter scale.

Subpackages: ``api`` (the declarative experiment layer:
ExecutionSpec/Plan/Session/RunSet + the capability registry), ``core``
(GP + GPCB), ``models`` (the arch zoo), ``dist`` (jitted GPFL
train/serve steps + sharding rules), ``fl`` (FL simulation: host loop +
compiled scan engines), ``kernels`` (Pallas), ``launch``
(drivers/dry-run), ``checkpoint``, ``data``, ``optim``, ``configs``,
``utils``.
"""
