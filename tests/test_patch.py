"""PATCH verb, strategic merge patch, and three-way kubectl apply.

Pins the reference semantics (pkg/util/strategicpatch/patch.go;
apiserver/pkg/endpoints/handlers/patch.go:51; kubectl apply's
CreateThreeWayMergePatch): merge-key lists, null deletes, $patch
directives, conflict behavior, and the apply-vs-controller ownership
contract VERDICT r3 called out (blind replace silently clobbered
controller-written fields)."""

import json
import os
import subprocess
import sys

import pytest

from kubernetes_tpu.api.objects import Deployment, Pod
from kubernetes_tpu.apiserver.store import Conflict, ObjectStore
from kubernetes_tpu.apiserver import strategicpatch as sp

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---- strategic merge unit semantics ----


def test_map_merge_and_null_delete():
    cur = {"a": 1, "b": {"x": 1, "y": 2}, "c": 3}
    patch = {"b": {"x": 9, "y": None}, "c": None, "d": 4}
    assert sp.strategic_merge(cur, patch) == {"a": 1, "b": {"x": 9}, "d": 4}


def test_merge_key_list_updates_by_key():
    cur = {"containers": [{"name": "app", "image": "v1"},
                          {"name": "sidecar", "image": "s1"}]}
    patch = {"containers": [{"name": "app", "image": "v2"}]}
    out = sp.strategic_merge(cur, patch)
    assert out["containers"] == [{"name": "app", "image": "v2"},
                                 {"name": "sidecar", "image": "s1"}]


def test_merge_key_list_delete_directive_and_append():
    cur = {"tolerations": [{"key": "a", "operator": "Exists"},
                           {"key": "b", "operator": "Exists"}]}
    patch = {"tolerations": [{"key": "a", "$patch": "delete"},
                             {"key": "c", "operator": "Exists"}]}
    out = sp.strategic_merge(cur, patch)
    assert out["tolerations"] == [{"key": "b", "operator": "Exists"},
                                  {"key": "c", "operator": "Exists"}]


def test_unkeyed_list_replaces_wholesale():
    cur = {"args": ["a", "b"]}
    assert sp.strategic_merge(cur, {"args": ["c"]}) == {"args": ["c"]}


def test_patch_replace_directive():
    cur = {"spec": {"a": 1, "b": 2}}
    out = sp.strategic_merge(cur, {"spec": {"$patch": "replace", "c": 3}})
    assert out == {"spec": {"c": 3}}


def test_json_merge_patch_lists_replace():
    cur = {"containers": [{"name": "app"}], "x": {"y": 1}}
    out = sp.json_merge(cur, {"containers": [{"name": "new"}],
                              "x": {"z": 2}})
    assert out == {"containers": [{"name": "new"}], "x": {"y": 1, "z": 2}}


def test_json_patch_ops():
    cur = {"spec": {"replicas": 1, "list": [1, 2]}}
    ops = [{"op": "test", "path": "/spec/replicas", "value": 1},
           {"op": "replace", "path": "/spec/replicas", "value": 5},
           {"op": "add", "path": "/spec/list/-", "value": 3},
           {"op": "remove", "path": "/spec/list/0"}]
    assert sp.json_patch(cur, ops) == {"spec": {"replicas": 5,
                                                "list": [2, 3]}}
    with pytest.raises(sp.PatchError):
        sp.json_patch(cur, [{"op": "test", "path": "/spec/replicas",
                             "value": 9}])


# ---- store PATCH verb ----


def _mkpod(store, name="p"):
    return store.create(Pod.from_dict({
        "metadata": {"name": name, "labels": {"app": "a"}},
        "spec": {"containers": [{"name": "c", "image": "v1"}]}}))


def test_store_patch_strategic_and_conflict_pin():
    store = ObjectStore()
    _mkpod(store)
    out = store.patch("Pod", "p", "default",
                      {"metadata": {"labels": {"tier": "web"}}},
                      sp.STRATEGIC)
    assert out.metadata.labels == {"app": "a", "tier": "web"}
    # pinned stale resourceVersion -> hard 409, no retry
    with pytest.raises(Conflict):
        store.patch("Pod", "p", "default",
                    {"metadata": {"resourceVersion": "1",
                                  "labels": {"x": "y"}}}, sp.STRATEGIC)


def test_patch_over_http_all_three_types():
    from http_util import http_store

    with http_store() as (client, _):
        _mkpod_remote(client)
        out = client.patch("Pod", "p", "default",
                           {"metadata": {"labels": {"tier": "web"}}},
                           sp.STRATEGIC)
        assert out.metadata.labels == {"app": "a", "tier": "web"}
        out = client.patch("Pod", "p", "default",
                           {"metadata": {"labels": {"only": "this"}}},
                           sp.MERGE)
        # merge patch merges maps too; labels is a map -> merged
        assert out.metadata.labels["only"] == "this"
        out = client.patch(
            "Pod", "p", "default",
            [{"op": "replace", "path": "/metadata/labels",
              "value": {"z": "1"}}], sp.JSONPATCH)
        assert out.metadata.labels == {"z": "1"}


def _mkpod_remote(client, name="p"):
    return client.create(Pod.from_dict({
        "metadata": {"name": name, "labels": {"app": "a"}},
        "spec": {"containers": [{"name": "c", "image": "v1"}]}}))


# ---- kubectl apply three-way ----


def _kubectl(url, *argv, manifest=None):
    cmd = [sys.executable, "-m", "kubernetes_tpu.cli.kubectl",
           "--server", url, *argv]
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO_ROOT)
    return subprocess.run(cmd, capture_output=True, text=True, timeout=90,
                          input=manifest, env=env)


DEPLOY_V1 = {
    "apiVersion": "apps/v1beta1", "kind": "Deployment",
    "metadata": {"name": "web", "namespace": "default"},
    "spec": {"selector": {"matchLabels": {"app": "web"}},
             "template": {
                 "metadata": {"labels": {"app": "web"}},
                 "spec": {"containers": [
                     {"name": "app", "image": "web:v1"},
                     {"name": "sidecar", "image": "sc:v1"}]}}}}


def test_apply_three_way_preserves_controller_writes(tmp_path):
    """VERDICT r3 done-criterion: apply twice while a 'controller' updates
    the live object between applies — both sides survive. The manifest
    never pins spec.replicas (the documented HPA-coexistence contract), so
    the controller's scale-up must survive the second apply; the dropped
    sidecar container, which apply DID own, must be deleted."""
    from http_util import http_store

    with http_store() as (client, _):
        url = f"http://{client.host}:{client.port}"
        f = tmp_path / "web.json"
        f.write_text(json.dumps(DEPLOY_V1))
        out = _kubectl(url, "apply", "-f", str(f))
        assert "created" in out.stdout, out.stdout + out.stderr

        # a controller writes fields the manifest doesn't carry: status and
        # a scale-up (like HPA would)
        live = client.get("Deployment", "web")
        live.status["observedGeneration"] = 7
        live.spec["replicas"] = 5
        client.update(live)

        # manifest changes the app image and DROPS the sidecar container
        doc2 = json.loads(json.dumps(DEPLOY_V1))
        doc2["spec"]["template"]["spec"]["containers"] = [
            {"name": "app", "image": "web:v2"}]
        f.write_text(json.dumps(doc2))
        out = _kubectl(url, "apply", "-f", str(f))
        assert "configured" in out.stdout, out.stdout + out.stderr

        after = client.get("Deployment", "web")
        containers = after.spec["template"]["spec"]["containers"]
        assert [c["name"] for c in containers] == ["app"]    # sidecar gone
        assert containers[0]["image"] == "web:v2"            # image applied
        assert after.spec["replicas"] == 5                   # HPA's survives
        assert after.status.get("observedGeneration") == 7   # status intact

        # idempotent re-apply
        out = _kubectl(url, "apply", "-f", str(f))
        assert "unchanged" in out.stdout, out.stdout + out.stderr


def test_apply_deletes_field_it_owned(tmp_path):
    """A field the previous apply set and the new manifest drops is
    deleted (apply ownership) — the reason HPA users un-pin replicas."""
    from http_util import http_store

    with http_store() as (client, _):
        url = f"http://{client.host}:{client.port}"
        doc = json.loads(json.dumps(DEPLOY_V1))
        doc["spec"]["replicas"] = 2
        f = tmp_path / "web.json"
        f.write_text(json.dumps(doc))
        assert "created" in _kubectl(url, "apply", "-f", str(f)).stdout
        assert client.get("Deployment", "web").spec["replicas"] == 2
        f.write_text(json.dumps(DEPLOY_V1))  # drops replicas
        out = _kubectl(url, "apply", "-f", str(f))
        assert "configured" in out.stdout, out.stdout + out.stderr
        assert "replicas" not in client.get("Deployment", "web").spec


def test_apply_adopts_kubectl_create_objects(tmp_path):
    """Apply over an object created without the last-applied annotation
    merges (original={}) without deleting anything it didn't own."""
    from http_util import http_store

    with http_store() as (client, _):
        url = f"http://{client.host}:{client.port}"
        client.create(Deployment.from_dict(DEPLOY_V1))
        doc = json.loads(json.dumps(DEPLOY_V1))
        doc["spec"]["replicas"] = 3
        f = tmp_path / "web.json"
        f.write_text(json.dumps(doc))
        out = _kubectl(url, "apply", "-f", str(f))
        assert "configured" in out.stdout, out.stdout + out.stderr
        after = client.get("Deployment", "web")
        assert after.spec["replicas"] == 3
        assert LAST_APPLIED_IN(after)


def LAST_APPLIED_IN(obj) -> bool:
    from kubernetes_tpu.cli.kubectl import LAST_APPLIED
    return LAST_APPLIED in (obj.metadata.annotations or {})


def test_kubectl_patch_label_annotate_verbs(tmp_path):
    from http_util import http_store

    with http_store() as (client, _):
        url = f"http://{client.host}:{client.port}"
        _mkpod_remote(client, "kp")
        out = _kubectl(url, "patch", "pod", "kp", "-p",
                       '{"metadata":{"labels":{"patched":"yes"}}}')
        assert "patched" in out.stdout, out.stdout + out.stderr
        assert client.get("Pod", "kp").metadata.labels["patched"] == "yes"
        out = _kubectl(url, "label", "pod", "kp", "tier=web", "patched-")
        assert "labeled" in out.stdout, out.stdout + out.stderr
        labels = client.get("Pod", "kp").metadata.labels
        assert labels.get("tier") == "web" and "patched" not in labels
        out = _kubectl(url, "annotate", "pod", "kp", "note=hi")
        assert "annotated" in out.stdout, out.stdout + out.stderr
        assert client.get("Pod", "kp").metadata.annotations["note"] == "hi"


def test_service_ports_merge_by_port_key():
    """ServicePort's patchMergeKey is 'port', not 'containerPort' — the
    candidate resolution must pick the key the items actually carry."""
    cur = {"ports": [{"port": 80, "targetPort": 8080},
                     {"port": 443, "targetPort": 8443}]}
    patch = {"ports": [{"port": 80, "targetPort": 9090}]}
    out = sp.strategic_merge(cur, patch)
    assert out["ports"] == [{"port": 80, "targetPort": 9090},
                            {"port": 443, "targetPort": 8443}]
    # and three-way diff round-trips through the same key
    frag = sp.create_three_way_patch(cur, patch, cur)
    assert sp.strategic_merge(cur, frag)["ports"][0]["targetPort"] == 9090


def test_apply_dropping_finalizers_preserves_controller_entries():
    """Dropping metadata.finalizers from the manifest removes only the
    values apply owned; a controller-added protection finalizer stays
    (deleteFromPrimitiveList semantics)."""
    original = {"metadata": {"finalizers": ["mine.io/f"]}}
    modified = {"metadata": {}}
    live = {"metadata": {"finalizers": ["mine.io/f", "protect.io/gc"]}}
    patch = sp.create_three_way_patch(original, modified, live)
    out = sp.strategic_merge(live, patch)
    assert out["metadata"]["finalizers"] == ["protect.io/gc"]


def test_json_patch_out_of_range_is_400_not_connection_drop():
    from http_util import http_store

    with http_store() as (client, _):
        _mkpod_remote(client, "oor")
        with pytest.raises(ValueError) as ei:
            client.patch("Pod", "oor", "default",
                         [{"op": "remove", "path": "/spec/containers/5"}],
                         sp.JSONPATCH)
        assert "400" in str(ei.value) or "bad JSON patch" in str(ei.value)
