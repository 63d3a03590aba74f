"""Compile: mean programs the first job on each of the run's graphs
needed that the process did not hold yet (jax's
``/jax/core/compile/jaxpr_to_mlir_module_duration`` events: one per
lowering, whether the program then compiles or loads from the persistent
cache).  A graph of a shape the program has not seen brings new ones."""


def read(run):
    cold = run.loop.cold_jobs
    if not cold:
        return None
    return sum(j.programs for j in cold) / len(cold)
