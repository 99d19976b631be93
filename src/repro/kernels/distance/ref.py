"""Pure-jnp oracle for the SiN distance kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.jit
def paged_distances_ref(page_ids: jax.Array, queries: jax.Array,
                        qq: jax.Array, db: jax.Array,
                        vnorm: jax.Array) -> jax.Array:
    """Same contract as kernels.distance.kernel.paged_distances."""
    pages = db[page_ids].astype(jnp.float32)        # (T, P, d)
    q = queries.astype(jnp.float32)
    qv = jnp.einsum("tqd,tpd->tqp", q, pages,
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)
    return (qq[:, :, None].astype(jnp.float32)
            - 2.0 * qv
            + vnorm[page_ids][:, None, :].astype(jnp.float32))
