"""Layer: kernels. How many registered kernels the registry's ``auto`` hands
their Pallas body on this cell's mesh (``selected_body`` is not
``reference``). A count that repeats exactly; a change of it between parent
and PR is the first thing to read beside ``pallas_time_pct``."""


def metric(facts):
    from paddle_tpu.ops import pallas
    with pallas.mesh_scope(facts["job"].mesh):
        return sum(pallas.selected_body(k) != "reference"
                   for k in pallas.list_kernels())
