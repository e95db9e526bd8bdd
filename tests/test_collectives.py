"""Ring collective-matmul tests (subprocess, 8 forced host devices):
numerical equality with the gathered reference + the all-gather actually
vanishing from the compiled module."""

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    return subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=420)


def test_ring_matmuls_in_serve_style_step():
    """The ring / psum-scatter collective matmuls inside ONE jitted
    serve-style step (embed -> up-proj via ring all-gather matmul ->
    activation -> down-proj via psum-scatter matmul -> logits argmax) on a
    forced-8-device host mesh, asserting token-level parity with the
    dense single-device reference — the shape the sharded decode engine
    (serve/shard.py) drives them in."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.parallel.mesh import make_mesh
        from repro.parallel.collectives import (ring_allgather_matmul,
                                                psum_scatter_matmul)

        mesh = make_mesh((2, 4), ("data", "model"))
        B, D, F, V = 8, 64, 128, 256
        k = jax.random.key(0)
        emb = jax.random.normal(jax.random.fold_in(k, 0), (V, D))
        w_up = jax.random.normal(jax.random.fold_in(k, 1), (D, F)) / D**0.5
        w_dn = jax.random.normal(jax.random.fold_in(k, 2), (F, D)) / F**0.5
        head = jax.random.normal(jax.random.fold_in(k, 3), (D, V)) / D**0.5
        toks = jax.random.randint(jax.random.fold_in(k, 4), (B,), 0, V)

        def step(tokens, emb, w_up, w_dn, head):
            # one serve-style decode step over the packed batch: the
            # activation rows ride the collective-matmul pair the way the
            # sharded engine's FFN does (gather-in, scatter-out)
            x = emb[tokens]                                   # (B, D)
            h = ring_allgather_matmul(x, w_up, mesh)          # (B, F)
            h = jax.nn.silu(h)
            y = psum_scatter_matmul(h, w_dn, mesh)            # (B, D)
            logits = y @ head
            return jnp.argmax(logits, axis=-1)

        def ref(tokens):
            x = emb[tokens]
            y = jax.nn.silu(x @ w_up) @ w_dn
            return jnp.argmax(y @ head, axis=-1)

        # place operands the way the collective matmuls expect them
        got = jax.jit(step)(toks, emb,
                            jax.device_put(w_up, NamedSharding(
                                mesh, P(None, "model"))),
                            jax.device_put(w_dn, NamedSharding(
                                mesh, P("model", None))),
                            head)
        want = ref(toks)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        txt = jax.jit(step).lower(toks, emb, w_up, w_dn, head
                                  ).compile().as_text()
        assert "collective-permute" in txt and "reduce-scatter" in txt
        print("RESULT ok")
    """)
    r = run_py(code)
    assert "RESULT ok" in r.stdout, (r.stdout[-1500:], r.stderr[-3000:])


def test_ring_matmul_reduce_matches_psum():
    """ring_matmul_reduce inside a shard_map body vs the blocking
    ``row_parallel_psum(h @ w, axis)`` it replaces — same operands, same
    call site, N dividing AND not dividing the shard count — plus the
    compiled HLO trading its all-reduce for collective-permutes (the
    overlappable form the decode epilogues switch to at overlap="ring")."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.parallel.mesh import make_mesh
        from repro.parallel.collectives import (ring_matmul_reduce,
                                                row_parallel_matmul,
                                                row_parallel_psum)

        n = 4
        mesh = make_mesh((1, n), ("data", "model"))
        for N in (128, 130, 7):      # dividing, +2 pad, N < shards
            B, K = 3, 64
            h = jax.random.normal(jax.random.key(N), (B, 2, K))
            w = jax.random.normal(jax.random.key(N + 1), (K, N)) / K**0.5

            def blocking(h, w):
                return row_parallel_psum(h @ w, "model")

            def ring(h, w):
                return row_parallel_matmul(h, w, "model", "ring")

            specs = dict(mesh=mesh, in_specs=(P(None, None, "model"),
                                              P("model", None)),
                         out_specs=P(), check_vma=False)
            want = jax.jit(shard_map(blocking, **specs))(h, w)
            got = jax.jit(shard_map(ring, **specs))(h, w)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-5, atol=2e-4)
            txt = jax.jit(shard_map(ring, **specs)).lower(h, w
                                                          ).compile().as_text()
            assert "collective-permute" in txt, N
            assert "all-reduce" not in txt, N
        # dispatcher: axis=None is the plain matmul; bad mode raises
        h2 = jax.random.normal(jax.random.key(9), (3, 2, 64))
        w2 = jax.random.normal(jax.random.key(10), (64, 16))
        np.testing.assert_array_equal(
            np.asarray(row_parallel_matmul(h2, w2, None, "ring")),
            np.asarray(h2 @ w2))
        try:
            row_parallel_matmul(h2, w2, None, "eager")
        except ValueError:
            pass
        else:
            raise AssertionError("bad overlap mode accepted")
        print("RESULT ok")
    """)
    r = run_py(code)
    assert "RESULT ok" in r.stdout, (r.stdout[-1500:], r.stderr[-3000:])


def test_ring_matmuls_pad_non_dividing_shapes():
    """Pad-and-slice: the standalone ring matmuls accept S / N that do
    not divide the shard count (zero rows/columns padded inside the
    jitted body, sliced back after) and still match the dense product."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.parallel.mesh import make_mesh
        from repro.parallel.collectives import (ring_allgather_matmul,
                                                psum_scatter_matmul)

        mesh = make_mesh((2, 4), ("data", "model"))
        for S, K, N in ((30, 64, 130), (5, 32, 3), (32, 64, 129)):
            x = jax.random.normal(jax.random.key(S), (S, K))
            w = jax.random.normal(jax.random.key(N), (K, N)) / K**0.5
            got = jax.jit(lambda a, b: ring_allgather_matmul(a, b, mesh)
                          )(x, w)
            np.testing.assert_allclose(np.asarray(got), np.asarray(x @ w),
                                       rtol=2e-5, atol=2e-4)
            got2 = jax.jit(lambda a, b: psum_scatter_matmul(a, b, mesh)
                           )(x, w)
            np.testing.assert_allclose(np.asarray(got2), np.asarray(x @ w),
                                       rtol=2e-5, atol=2e-4)
        print("RESULT ok")
    """)
    r = run_py(code)
    assert "RESULT ok" in r.stdout, (r.stdout[-1500:], r.stderr[-3000:])


def test_sharded_engine_overlap_ring_byte_identical():
    """Acceptance seam of the overlap PR: greedy decode tokens from the
    tensor-parallel engine with overlap="ring" AND pipeline="double" are
    byte-identical to the single-device serial engine — GQA (qwen3) and
    a dense-FFN MLA config (MoE blocks need expert parallelism, a
    different seam), tp=2 on a forced-8-device mesh."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses
        import jax, numpy as np
        from repro.configs import get_config, smoke
        from repro.models import init_params
        from repro.models.common import BlockDef
        from repro.serve import (EngineConfig, GenerateConfig, make_engine,
                                 tp_sharding_error)

        def tokens(cfg, params, mesh, pipeline, overlap):
            eng = make_engine(cfg, params, EngineConfig(
                num_slots=2, page_size=4, max_len=32,
                pipeline=pipeline, overlap=overlap), mesh_shape=mesh)
            gen = GenerateConfig(max_new_tokens=6)
            prompts = [np.asarray(jax.random.randint(
                jax.random.key(50 + i), (5 + i,), 0, cfg.vocab_size),
                np.int32) for i in range(3)]
            reqs = [eng.submit(p, gen) for p in prompts]
            eng.run()
            return [list(r.generated) for r in reqs]

        gqa = smoke(get_config("qwen3-0.6b"))
        mla = dataclasses.replace(
            smoke(get_config("deepseek-v2-236b")), name="mla-dense-smoke",
            n_experts=0, moe_top_k=0, moe_d_ff=0, n_shared_experts=0,
            moe_first_dense=0, n_layers=2,
            block_pattern=(BlockDef("mla", "dense"),))
        for cfg in (gqa, mla):
            assert tp_sharding_error(cfg, 2) is None, cfg.name
            params = init_params(cfg, jax.random.key(0))
            base = tokens(cfg, params, (1, 1), "off", "none")
            got = tokens(cfg, params, (1, 2), "double", "ring")
            assert got == base, (cfg.name, got, base)
        print("RESULT ok")
    """)
    r = run_py(code)
    assert "RESULT ok" in r.stdout, (r.stdout[-1500:], r.stderr[-3000:])


def test_ring_matmuls_match_reference():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.parallel.mesh import make_mesh
        from repro.parallel.collectives import (ring_allgather_matmul,
                                                psum_scatter_matmul)

        mesh = make_mesh((2, 4), ("data", "model"))
        S, K, N = 32, 64, 128
        x = jax.random.normal(jax.random.key(0), (S, K))
        w = jax.random.normal(jax.random.key(1), (K, N))

        got = jax.jit(lambda a, b: ring_allgather_matmul(a, b, mesh))(x, w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(x @ w),
                                   rtol=2e-5, atol=2e-4)
        # the ring form contains ppermutes, not an all-gather of x
        txt = jax.jit(lambda a, b: ring_allgather_matmul(a, b, mesh)
                      ).lower(x, w).compile().as_text()
        assert "collective-permute" in txt

        got2 = jax.jit(lambda a, b: psum_scatter_matmul(a, b, mesh))(x, w)
        np.testing.assert_allclose(np.asarray(got2), np.asarray(x @ w),
                                   rtol=2e-5, atol=2e-4)
        txt2 = jax.jit(lambda a, b: psum_scatter_matmul(a, b, mesh)
                       ).lower(x, w).compile().as_text()
        assert "reduce-scatter" in txt2
        print("RESULT ok")
    """)
    r = run_py(code)
    assert "RESULT ok" in r.stdout, (r.stdout[-1500:], r.stderr[-3000:])
