# The benchmark's tests run on the CPU at small sizes. JAX's persistent
# compilation cache stays off: pytest's workers would share one directory,
# and its writes are not atomic.
import jax

jax.config.update("jax_enable_compilation_cache", False)
