"""CLI + admin + dashboard + export/import tests (reference Console specs +
AdminAPISpec)."""

import json
import urllib.request

import pytest

from pio_tpu.data.storage import set_storage
from pio_tpu.tools.cli import main


# the `cli` fixture lives in conftest.py (shared with test_cli_verbs.py)


def test_version_and_status(cli):
    code, out = cli("version")
    assert code == 0 and out.out.strip()
    code, out = cli("status")
    assert code == 0
    assert "sanity check passed" in out.out


def test_run_script(cli, tmp_path):
    script = tmp_path / "job.py"
    script.write_text(
        "import sys\n"
        "from pio_tpu.data.storage import get_storage\n"
        "s = get_storage()\n"
        "s.get_metadata_apps()  # storage reachable\n"
        "print('ran with', sys.argv[1])\n"
    )
    code, out = cli("run", str(script), "arg1")
    assert code == 0
    assert "ran with arg1" in out.out


def test_run_missing_script(cli, tmp_path):
    code, out = cli("run", str(tmp_path / "nope.py"))
    assert code == 1


def test_app_lifecycle(cli):
    code, out = cli("app", "new", "myapp", "--description", "d")
    assert code == 0 and "Access key:" in out.out
    code, out = cli("app", "new", "myapp")
    assert code == 1  # duplicate
    code, out = cli("app", "list")
    assert "myapp" in out.out
    code, out = cli("app", "show", "myapp")
    assert code == 0 and "channel" not in out.out.lower()
    code, out = cli("app", "channel-new", "myapp", "mobile")
    assert code == 0
    code, out = cli("app", "channel-new", "myapp", "bad name!")
    assert code == 1
    code, out = cli("app", "show", "myapp")
    assert "mobile" in out.out
    code, out = cli("app", "data-delete", "myapp")
    assert code == 0
    code, out = cli("app", "channel-delete", "myapp", "mobile")
    assert code == 0
    code, out = cli("app", "delete", "myapp")
    assert code == 0
    code, out = cli("app", "show", "myapp")
    assert code == 1


def test_accesskey_lifecycle(cli):
    cli("app", "new", "keyapp")
    code, out = cli("accesskey", "new", "keyapp", "--event", "rate")
    assert code == 0
    key = out.out.strip().split()[-1]
    code, out = cli("accesskey", "list", "keyapp")
    assert key in out.out and "rate" in out.out
    code, out = cli("accesskey", "delete", key)
    assert code == 0
    code, out = cli("accesskey", "new", "ghost")
    assert code == 1


def test_build_train_deploy_roundtrip(cli, memory_storage, tmp_path):
    import numpy as np
    from datetime import datetime, timedelta, timezone
    from pio_tpu.data import DataMap, Event

    cli("app", "new", "mlapp")
    app_id = memory_storage.get_metadata_apps().get_by_name("mlapp").id
    ev = memory_storage.get_events()
    rng = np.random.default_rng(0)
    T0 = datetime(2026, 1, 1, tzinfo=timezone.utc)
    m = 0
    for u in range(16):
        for i in range(10):
            if rng.random() < (0.8 if (u % 2) == (i % 2) else 0.1):
                ev.insert(Event(
                    event="rate", entity_type="user", entity_id=f"u{u}",
                    target_entity_type="item", target_entity_id=f"i{i}",
                    properties=DataMap({"rating": 5 if (u % 2) == (i % 2) else 1}),
                    event_time=T0 + timedelta(minutes=m)), app_id)
                m += 1

    engine_dir = tmp_path / "eng"
    engine_dir.mkdir()
    (engine_dir / "engine.json").write_text(json.dumps({
        "id": "clirec",
        "engineFactory": "pio_tpu.models.recommendation.RecommendationEngine",
        "datasource": {"params": {"app_name": "mlapp"}},
        "algorithms": [{"name": "als", "params": {
            "rank": 4, "num_iterations": 4, "lambda_": 0.05, "chunk": 1024}}],
    }))

    code, out = cli("build", "--engine-dir", str(engine_dir))
    assert code == 0 and "loads" in out.out

    code, out = cli("train", "--engine-dir", str(engine_dir), "--no-mesh")
    assert code == 0 and "Training completed" in out.out
    instances = memory_storage.get_metadata_engine_instances()
    assert instances.get_latest_completed("clirec", "1", "default")

    # interruption flags: controlled stop, exit 0
    code, out = cli("train", "--engine-dir", str(engine_dir), "--no-mesh",
                    "--stop-after-read")
    assert code == 0 and "interrupted" in out.out.lower()

    # a device profile of the whole train: the job's spans lie on the
    # trace's host plane (no device plane on the CPU backend)
    from pio_tpu.obs import profile

    prof = tmp_path / "prof"
    code, out = cli("train", "--engine-dir", str(engine_dir), "--no-mesh",
                    "--device-profile", str(prof))
    assert code == 0 and "python -m pio_tpu.obs.profile" in out.out
    spans = {name for name, _, _ in profile.read_profile(str(prof))["spans"]}
    assert {"train", "train.algorithms", "als.dispatch",
            "persist.insert"} <= spans


def test_build_missing_engine_json(cli, tmp_path):
    code, out = cli("build", "--engine-dir", str(tmp_path))
    assert code == 1 and "engine.json" in out.err


def test_template_new(cli, tmp_path):
    target = tmp_path / "myengine"
    code, out = cli("template", "new", str(target))
    assert code == 0
    assert (target / "engine.json").exists()
    assert (target / "engine.py").exists()
    variant = json.loads((target / "engine.json").read_text())
    assert variant["engineFactory"] == "engine.MyEngine"
    # refuses to overwrite
    code, out = cli("template", "new", str(target))
    assert code == 1


@pytest.fixture()
def gallery_server(tmp_path):
    """A local HTTP gallery (reference Template.scala's remote index,
    testable without egress): index.json + one template with a trainable
    engine.json + an extra data file in a subdirectory."""
    import http.server
    import threading

    root = tmp_path / "gallery"
    tdir = root / "acme-rec"
    (tdir / "data").mkdir(parents=True)
    (root / "index.json").write_text(json.dumps([{
        "name": "acme-rec",
        "description": "ACME's tuned recommender",
        "files": ["engine.json", "data/notes.txt"],
    }]))
    (tdir / "engine.json").write_text(json.dumps({
        "id": "acme-rec",
        "description": "ACME's tuned recommender",
        "engineFactory":
            "pio_tpu.models.recommendation.RecommendationEngine",
        "datasource": {"params": {"app_name": "acmeapp"}},
        "algorithms": [{"name": "als", "params": {
            "rank": 4, "num_iterations": 3, "lambda_": 0.05,
            "chunk": 512}}],
    }))
    (tdir / "data" / "notes.txt").write_text("hello from the gallery\n")

    handler = type("H", (http.server.SimpleHTTPRequestHandler,), {
        "directory": str(root),
        "log_message": lambda *a, **k: None,
    })
    srv = http.server.ThreadingHTTPServer(
        ("127.0.0.1", 0), lambda *a, **k: handler(*a, directory=str(root),
                                                  **k))
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    yield f"http://127.0.0.1:{srv.server_address[1]}"
    srv.shutdown()


def test_template_remote_gallery(cli, gallery_server, tmp_path):
    """Remote gallery: list merges remote entries, new downloads the
    declared files, and the scaffold trains through the normal CLI path
    (reference console/Template.scala:130-429 fetch-and-scaffold)."""
    code, out = cli("template", "list", "--gallery-url", gallery_server)
    assert code == 0
    assert "acme-rec" in out.out and "[remote]" in out.out

    target = tmp_path / "from-remote"
    code, out = cli("template", "new", str(target),
                    "--template", "acme-rec",
                    "--gallery-url", gallery_server)
    assert code == 0, out.err
    assert (target / "data" / "notes.txt").read_text().startswith("hello")
    variant = json.loads((target / "engine.json").read_text())
    assert variant["engineFactory"].endswith("RecommendationEngine")
    code, out = cli("build", "--engine-dir", str(target))
    assert code == 0, out.err
    # scaffold trains as-is once its app exists
    code, out = cli("app", "new", "acmeapp")
    assert code == 0
    from pio_tpu.data import DataMap, Event
    from pio_tpu.data.storage import get_storage

    storage = get_storage()
    app_id = storage.get_metadata_apps().get_by_name("acmeapp").id
    ev = storage.get_events()
    for u in range(12):
        for i in range(8):
            if (u + i) % 2 == 0:
                ev.insert(Event(
                    event="rate", entity_type="user", entity_id=f"u{u}",
                    target_entity_type="item", target_entity_id=f"i{i}",
                    properties=DataMap({"rating": 5})), app_id)
    code, out = cli("train", "--engine-dir", str(target))
    assert code == 0, out.err


def test_template_remote_gallery_errors(cli, tmp_path, monkeypatch):
    """Unreachable gallery and unsafe file paths fail cleanly."""
    code, out = cli("template", "list",
                    "--gallery-url", "http://127.0.0.1:1")
    assert code == 1 and "gallery fetch failed" in out.err

    from pio_tpu.tools.templates import GalleryError, fetch_gallery

    class FakeResp:
        def __init__(self, body):
            self.body = body

        def read(self):
            return self.body

        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False

    import urllib.request

    def serve(body):
        monkeypatch.setattr(
            urllib.request, "urlopen",
            lambda url, timeout=0: FakeResp(body))

    for rel in ("../../etc/passwd", "..\\..\\evil.py", "C:/evil",
                "/abs/path", "", "data/", "a/../b"):
        serve(json.dumps([{"name": "evil", "files": [rel]}]).encode())
        with pytest.raises(GalleryError, match="unsafe"):
            fetch_gallery("http://gallery.example")
    # malformed index shapes fail cleanly, not with raw tracebacks
    for body in (b'["just-a-string"]', b'[{"files": [123]}]', b'{"x": 1}'):
        serve(body)
        with pytest.raises(GalleryError):
            fetch_gallery("http://gallery.example")
    # non-http scheme rejected before any fetch
    with pytest.raises(GalleryError, match="http"):
        fetch_gallery("file:///etc")


def test_template_builtin_works_with_dead_env_gallery(
        cli, tmp_path, monkeypatch):
    """A down gallery configured via env var must not block builtin
    scaffolds (no network needed), and `list` degrades with a warning."""
    monkeypatch.setenv("PIO_TEMPLATE_GALLERY_URL", "http://127.0.0.1:1")
    target = tmp_path / "local-eng"
    code, out = cli("template", "new", str(target))
    assert code == 0, out.err
    assert (target / "engine.json").exists()
    code, out = cli("template", "list")
    assert code == 0
    assert "recommendation" in out.out
    assert "WARN" in out.err


def test_template_gallery_every_shape_builds(cli, tmp_path):
    """`pio template list` + one scaffold per zoo shape, each passing
    `pio build` untouched (reference console/Template.scala gallery,
    offline: the gallery IS the zoo)."""
    from pio_tpu.tools.templates import TEMPLATES

    code, out = cli("template", "list")
    assert code == 0
    for name in ("recommendation", "classification", "similarproduct",
                 "ecommerce", "twotower", "sequence", "custom"):
        assert name in TEMPLATES and name in out.out

    for name in TEMPLATES:
        target = tmp_path / name
        code, out = cli("template", "new", str(target), "--template", name)
        assert code == 0, out.err
        assert (target / "engine.json").exists()
        assert (target / "README.md").exists()
        code, out = cli("build", "--engine-dir", str(target))
        assert code == 0, f"{name}: {out.err}"
        assert "loads" in out.out

    code, out = cli("template", "new", str(tmp_path / "x"),
                    "--template", "nope")
    assert code == 1 and "unknown template" in out.err


def test_export_import(cli, memory_storage, tmp_path):
    from pio_tpu.data import DataMap, Event

    cli("app", "new", "exapp")
    app_id = memory_storage.get_metadata_apps().get_by_name("exapp").id
    ev = memory_storage.get_events()
    for i in range(5):
        ev.insert(Event(event="rate", entity_type="user", entity_id=f"u{i}",
                        target_entity_type="item", target_entity_id="i1",
                        properties=DataMap({"rating": i})), app_id)
    out_file = tmp_path / "events.jsonl"
    code, out = cli("export", "--appid", str(app_id),
                    "--output", str(out_file))
    assert code == 0 and "Exported 5" in out.out

    cli("app", "new", "imapp")
    app2 = memory_storage.get_metadata_apps().get_by_name("imapp").id
    code, out = cli("import", "--appid", str(app2), "--input", str(out_file))
    assert code == 0 and "Imported 5" in out.out
    assert len(list(ev.find(app2, limit=-1))) == 5

    # corrupt line counts as failure but doesn't abort
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"event": "x", "entityType": "u", "entityId": "1"}\nnot json\n')
    cli("app", "new", "badapp")
    app3 = memory_storage.get_metadata_apps().get_by_name("badapp").id
    code, out = cli("import", "--appid", str(app3), "--input", str(bad))
    assert code == 1 and "Imported 1 events (1 failed)" in out.out


def test_export_import_parquet(cli, memory_storage, tmp_path):
    """Columnar round-trip (reference EventsToFile.scala:39 parquet format):
    full field fidelity incl. properties/tags/times/prId, format inferred
    from the .parquet extension, and a bulk round-trip of many
    events."""
    from datetime import datetime, timezone

    from pio_tpu.data import DataMap, Event

    T0 = datetime(2026, 2, 3, 4, 5, 6, tzinfo=timezone.utc)
    cli("app", "new", "pqapp")
    app_id = memory_storage.get_metadata_apps().get_by_name("pqapp").id
    ev = memory_storage.get_events()
    rich = Event(
        event="buy", entity_type="user", entity_id="u1",
        target_entity_type="item", target_entity_id="i9",
        properties=DataMap({"price": 3.5, "tags": ["a", "b"], "n": 2}),
        event_time=T0, tags=("t1", "t2"), pr_id="pr-7",
    )
    rich_id = ev.insert(rich, app_id)
    ev.insert(Event(event="view", entity_type="user", entity_id="u2"), app_id)
    for i in range(100_00):
        ev.insert(Event(event="rate", entity_type="user", entity_id=f"u{i}",
                        target_entity_type="item", target_entity_id="i1",
                        properties=DataMap({"rating": i % 5})), app_id)

    out_file = tmp_path / "events.parquet"
    code, out = cli("export", "--appid", str(app_id),
                    "--output", str(out_file))
    assert code == 0 and "Exported 10002" in out.out

    cli("app", "new", "pqapp2")
    app2 = memory_storage.get_metadata_apps().get_by_name("pqapp2").id
    code, out = cli("import", "--appid", str(app2), "--input", str(out_file))
    assert code == 0 and "Imported 10002 events (0 failed)" in out.out

    got = {e.entity_id: e for e in ev.find(app2, event_names=["buy", "view"],
                                           limit=-1)}
    r = got["u1"]
    assert r.event == "buy" and r.target_entity_id == "i9"
    assert dict(r.properties.fields) == {"price": 3.5, "tags": ["a", "b"], "n": 2}
    assert r.event_time.astimezone(timezone.utc) == T0
    assert r.tags == ("t1", "t2") and r.pr_id == "pr-7"
    assert r.event_id == rich_id  # ids survive the round trip
    bare = got["u2"]
    assert bare.target_entity_type is None and not bare.properties.fields


def test_admin_server(memory_storage):
    from pio_tpu.tools.admin import create_admin_server

    srv = create_admin_server(memory_storage, ip="127.0.0.1", port=0).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"

        def call(method, path, body=None):
            data = json.dumps(body).encode() if body is not None else None
            req = urllib.request.Request(base + path, data=data, method=method)
            try:
                with urllib.request.urlopen(req) as r:
                    return r.status, json.loads(r.read().decode())
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read().decode() or "{}")

        status, body = call("POST", "/cmd/app", {"name": "adminapp"})
        assert status == 200 and body["accessKey"]
        status, body = call("POST", "/cmd/app", {"name": "adminapp"})
        assert status == 409
        status, body = call("GET", "/cmd/app")
        assert [a["name"] for a in body["apps"]] == ["adminapp"]
        status, body = call("DELETE", "/cmd/app/adminapp/data")
        assert status == 200
        status, body = call("DELETE", "/cmd/app/adminapp")
        assert status == 200
        status, body = call("GET", "/cmd/app")
        assert body["apps"] == []
        status, _ = call("DELETE", "/cmd/app/ghost")
        assert status == 404
    finally:
        srv.stop()


def test_dashboard(memory_storage):
    import urllib.error
    from datetime import datetime, timezone
    from pio_tpu.data.dao import EvaluationInstance
    from pio_tpu.tools.dashboard import create_dashboard

    dao = memory_storage.get_metadata_evaluation_instances()
    iid = dao.insert(EvaluationInstance(
        id="", status="EVALCOMPLETED",
        start_time=datetime(2026, 1, 1, tzinfo=timezone.utc),
        end_time=datetime(2026, 1, 1, tzinfo=timezone.utc),
        evaluation_class="MyEval", evaluator_results="[0.9] {...}",
        evaluator_results_html="<h2>Metric</h2><table></table>",
        evaluator_results_json='{"bestScore": 0.9}',
    ))
    srv = create_dashboard(memory_storage, ip="127.0.0.1", port=0).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        page = urllib.request.urlopen(base + "/").read().decode()
        assert "MyEval" in page and iid in page
        detail = urllib.request.urlopen(
            base + f"/engine_instances/{iid}/evaluator_results.html"
        ).read().decode()
        assert "<table>" in detail
        j = json.loads(urllib.request.urlopen(
            base + f"/engine_instances/{iid}/evaluator_results.json"
        ).read().decode())
        assert j["bestScore"] == 0.9
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                base + "/engine_instances/nope/evaluator_results.html")
    finally:
        srv.stop()


def test_import_batches_and_isolates_bad_batch(cli, memory_storage,
                                               tmp_path, monkeypatch):
    """Imports flush in IMPORT_BATCH bulk writes (one RPC per batch on a
    remote store); a bulk write that fails retries singly so exactly the
    bad events count as failures."""
    import json as _json

    from pio_tpu.tools import export_import as ei

    monkeypatch.setattr(ei, "IMPORT_BATCH", 3)
    cli("app", "new", "batchimp")
    app_id = memory_storage.get_metadata_apps().get_by_name("batchimp").id
    f = tmp_path / "in.jsonl"
    f.write_text("".join(
        _json.dumps({"event": "rate", "entityType": "user",
                     "entityId": f"u{i}"}) + "\n"
        for i in range(8)))
    code, out = cli("import", "--appid", str(app_id), "--input", str(f))
    assert code == 0 and "Imported 8" in out.out
    ev = memory_storage.get_events()
    assert len(list(ev.find(app_id, limit=-1))) == 8

    # a poisoned batch (insert_batch raises) falls back to per-event
    calls = {"batch": 0}

    def bad_batch(self, events, app_id_, channel_id=None):
        calls["batch"] += 1
        raise RuntimeError("bulk path down")

    # patch the BACKING DAO class: `ev` is normally a ResilientDAO proxy,
    # whose type() is the proxy class (isinstance sees through via
    # __class__, type() does not); fresh proxies pick the patched method
    # up. getattr fallback keeps this valid under PIO_TPU_RESILIENCE=off.
    monkeypatch.setattr(type(getattr(ev, "_dao", ev)),
                        "insert_batch", bad_batch)
    cli("app", "new", "fallbackimp")
    app2 = memory_storage.get_metadata_apps().get_by_name("fallbackimp").id
    code, out = cli("import", "--appid", str(app2), "--input", str(f))
    assert code == 0 and "Imported 8" in out.out and calls["batch"] >= 1
    assert len(list(ev.find(app2, limit=-1))) == 8


def test_import_partial_batch_failure_no_duplicates(cli, memory_storage,
                                                    tmp_path, monkeypatch):
    """The hard case: insert_batch persists PART of a batch then dies
    (a remote RPC can time out after the server committed). The
    per-event retry must skip what already landed — ids are minted
    client-side so the check is exact — never duplicate it."""
    import json as _json

    from pio_tpu.tools import export_import as ei

    monkeypatch.setattr(ei, "IMPORT_BATCH", 4)
    cli("app", "new", "partialimp")
    app_id = memory_storage.get_metadata_apps().get_by_name("partialimp").id
    ev = memory_storage.get_events()
    # patch the backing DAO class, not the ResilientDAO proxy (see
    # test_import_batches_and_isolates_bad_batch)
    backing_cls = type(getattr(ev, "_dao", ev))
    real_batch = backing_cls.insert_batch

    def half_then_die(self, events, app_id_, channel_id=None):
        real_batch(self, events[: len(events) // 2], app_id_, channel_id)
        raise RuntimeError("died mid-batch")

    monkeypatch.setattr(backing_cls, "insert_batch", half_then_die)
    f = tmp_path / "in.jsonl"
    f.write_text("".join(
        _json.dumps({"event": "rate", "entityType": "user",
                     "entityId": f"u{i}"}) + "\n"
        for i in range(8)))
    code, out = cli("import", "--appid", str(app_id), "--input", str(f))
    assert code == 0 and "Imported 8" in out.out
    got = list(ev.find(app_id, limit=-1))
    assert len(got) == 8                                   # no duplicates
    assert len({e.entity_id for e in got}) == 8
