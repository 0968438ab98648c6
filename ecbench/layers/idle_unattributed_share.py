"""Per cent of the traced slice's device-idle time during which no
`sw:<op>/<stage>` annotation of the program was open on any host thread
(volume cells). The table of idle seconds by stage goes to standard
error."""

from ecbench.spanlib import idle_unattributed_share


def read(obs, cell):
    return idle_unattributed_share(obs)
