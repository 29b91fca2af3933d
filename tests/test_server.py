"""marian-server: end-to-end protocol tests against the REAL _serve wiring
(server/server.py — reference src/command/marian_server.cpp; the serving
subsystem behind it is unit-tested in tests/test_serving.py).

Two transports, one ServingApp: the Marian WebSocket protocol (gated on the
``websockets`` package) and the dependency-free length-prefixed TCP framing
the server falls back to without it — so a real-model round trip is
exercised in every environment."""

import asyncio

import numpy as np
import pytest

from marian_tpu.common import Options
from marian_tpu.data.vocab import DefaultVocab


def _tiny_server_options(tmp_path, seed=2):
    """Build + save a tiny real model; returns server-mode Options."""
    import jax
    from marian_tpu.common import io as mio
    from marian_tpu.models.encoder_decoder import create_model

    words = [f"w{i}" for i in range(20)]
    vocab = DefaultVocab.build([" ".join(words)])
    vpath = tmp_path / "v.yml"
    vocab.save(str(vpath))
    opts = Options({"type": "transformer", "dim-emb": 16,
                    "transformer-heads": 2, "transformer-dim-ffn": 32,
                    "enc-depth": 1, "dec-depth": 1,
                    "tied-embeddings-all": True, "max-length": 16,
                    "precision": ["float32", "float32"], "seed": seed})
    model = create_model(opts, len(vocab), len(vocab), inference=True)
    params = model.init(jax.random.key(seed))
    mpath = tmp_path / "m.npz"
    mio.save_model(str(mpath), {k: np.asarray(v) for k, v in params.items()},
                   opts.as_yaml())
    return Options({"models": [str(mpath)], "vocabs": [str(vpath),
                                                       str(vpath)],
                    "beam-size": 2, "max-length": 16, "port": 0,
                    "mini-batch": 8, "max-queue": 64,
                    "batch-token-budget": 128})


async def _drive_serve(sopts, client_fn):
    """Start the REAL _serve (scheduler, admission, transport) on an
    ephemeral port, run client_fn(port), tear down."""
    from marian_tpu.server import server as srv
    loop = asyncio.get_event_loop()
    ready = loop.create_future()
    server_task = asyncio.ensure_future(srv._serve(sopts, ready=ready))
    port = await asyncio.wait_for(ready, 60)
    try:
        return await client_fn(port)
    finally:
        server_task.cancel()
        try:
            await server_task
        except (asyncio.CancelledError, Exception):
            pass


async def _tcp_request(port: int, text: str) -> str:
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    payload = text.encode("utf-8")
    writer.write(b"MTPU %d\n" % len(payload) + payload)
    await writer.drain()
    header = await reader.readline()
    assert header.startswith(b"MTPU ")
    reply = await reader.readexactly(int(header.split()[1]))
    writer.close()
    return reply.decode("utf-8")


def test_server_e2e_websocket(tmp_path):
    """Real model, real websocket round trip, two concurrent clients."""
    websockets = pytest.importorskip("websockets")
    from marian_tpu.server import server as srv
    if not srv.HAVE_WS:  # pragma: no cover — importorskip above covers it
        pytest.skip("server module loaded without websockets")

    sopts = _tiny_server_options(tmp_path)

    async def clients(port):
        async def client(text):
            async with websockets.connect(f"ws://127.0.0.1:{port}") as ws:
                await ws.send(text)
                return await ws.recv()

        return await asyncio.gather(client("w3 w4 w5"),
                                    client("w6 w7\nw8 w9"))

    r1, r2 = asyncio.run(_drive_serve(sopts, clients))
    assert isinstance(r1, str)
    assert r2.count("\n") == 1          # two sentences → two reply lines


def test_readyz_waits_for_the_listener(tmp_path, monkeypatch):
    """/readyz must not read ready between ServingApp.start() and the
    request port's bind: a client that trusts it would be refused (the
    chip run of chip_smoke.py hit exactly that window)."""
    from marian_tpu.server import server as srv
    seen = {}
    start = srv.ServingApp.start

    async def start_and_look(self):
        await start(self)
        seen["app"], seen["ready_before_bind"] = self, self.ready()
    monkeypatch.setattr(srv.ServingApp, "start", start_and_look)

    async def after_bind(port):
        return seen["app"].ready()

    ready_after_bind = asyncio.run(
        _drive_serve(_tiny_server_options(tmp_path), after_bind))
    assert seen["ready_before_bind"] is False
    assert ready_after_bind is True


def test_server_e2e_tcp_fallback(tmp_path, monkeypatch):
    """Real model over the dependency-free TCP framing — the transport
    _serve falls back to without websockets (forced here so the test is
    deterministic in every environment)."""
    from marian_tpu.server import server as srv
    monkeypatch.setattr(srv, "HAVE_WS", False)

    sopts = _tiny_server_options(tmp_path)

    async def clients(port):
        return await asyncio.gather(
            _tcp_request(port, "w3 w4 w5"),
            _tcp_request(port, "w6 w7\nw8 w9"))

    r1, r2 = asyncio.run(_drive_serve(sopts, clients))
    assert isinstance(r1, str)
    assert r2.count("\n") == 1


def test_server_e2e_iteration_beam_with_prefix_cache(tmp_path, monkeypatch):
    """ISSUE 12 acceptance leg: the server no longer refuses beam>1 in
    iteration mode — COW-paged beam serving works end-to-end on the
    real CPU server (TCP framing), with --prefix-cache turning an
    exact repeat into a hit whose reply is identical to the cold one
    (deterministic decode)."""
    from marian_tpu.server import server as srv
    monkeypatch.setattr(srv, "HAVE_WS", False)

    # seed 19 decodes a short nonempty output WITH a mid-decode EOS (one
    # hypothesis freezes while its sibling continues — the COW path's
    # page-free-at-freeze leg runs on the real server)
    base = _tiny_server_options(tmp_path, seed=19)
    dense = srv.TranslationService(base).translate_lines(["w3 w4 w5"])
    assert dense[0], "this seed's random model decodes '' — pick another"
    sopts = base.with_(**{
        "batching-mode": "iteration", "beam-size": 2,
        "iteration-rows": 8, "kv-page-len": 4,
        "prefix-cache": True})

    async def clients(port):
        cold = await _tcp_request(port, "w3 w4 w5")
        warm = await _tcp_request(port, "w3 w4 w5")   # exact repeat
        multi = await _tcp_request(port, "w6 w7\nw8 w9")
        return cold, warm, multi

    cold, warm, multi = asyncio.run(_drive_serve(sopts, clients))
    assert cold and not cold.startswith("!!SERVER-")
    assert cold == dense[0]              # paged beam == dense beam
    assert warm == cold                  # prefix replay == cold decode
    assert multi.count("\n") == 1
