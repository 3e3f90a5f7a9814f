"""Operations and bytes of one kernel call, one module per kernel.

Each module gives ``EVENT``, the name of the kernel's custom call in the
device trace (``<EVENT>`` or ``<EVENT>.<n>``), and ``count(**call) -> (flops, bytes)``, where ``flops``
is ``{precision class: operations}`` as ``peaks.json`` names the classes.
Work the kernel repeats or pads is not counted: the counts are what the
algorithm needs."""
