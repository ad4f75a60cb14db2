"""RBAC authorizer + TLS serving.

Pins plugin/pkg/auth/authorizer/rbac/rbac.go:43 rule matching (bindings ->
roles -> PolicyRules, '*' wildcards, RoleBinding namespace scoping,
ClusterRoleBinding cluster grants, ServiceAccount subjects), chaining with
ABAC (union, like --authorization-mode=ABAC,RBAC), and secure serving
(apiserver/pkg/server/secure_serving.go) end to end over HTTPS."""

import json
import os
import subprocess

import pytest

from kubernetes_tpu.api.objects import (
    ClusterRole,
    ClusterRoleBinding,
    Pod,
    Role,
    RoleBinding,
)
from kubernetes_tpu.apiserver import ObjectStore
from kubernetes_tpu.apiserver.auth import (
    ABACAuthorizer,
    RBACAuthorizer,
    TokenAuthenticator,
    UnionAuthorizer,
    UserInfo,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ALICE = UserInfo(name="alice", groups=("devs",))
BOB = UserInfo(name="bob", groups=())
SA = UserInfo(name="system:serviceaccount:default:robot", groups=())


def _store_with_rbac():
    store = ObjectStore()
    store.create(Role.from_dict({
        "metadata": {"name": "pod-reader", "namespace": "default"},
        "rules": [{"apiGroups": [""], "resources": ["pods"],
                   "verbs": ["get", "list", "watch"]}]}))
    store.create(RoleBinding.from_dict({
        "metadata": {"name": "alice-reads", "namespace": "default"},
        "subjects": [{"kind": "User", "name": "alice"}],
        "roleRef": {"kind": "Role", "name": "pod-reader"}}))
    store.create(ClusterRole.from_dict({
        "metadata": {"name": "node-admin"},
        "rules": [{"apiGroups": [""], "resources": ["nodes"],
                   "verbs": ["*"]}]}))
    store.create(ClusterRoleBinding.from_dict({
        "metadata": {"name": "devs-node-admin"},
        "subjects": [{"kind": "Group", "name": "devs"}],
        "roleRef": {"kind": "ClusterRole", "name": "node-admin"}}))
    store.create(RoleBinding.from_dict({
        "metadata": {"name": "robot-reads", "namespace": "default"},
        "subjects": [{"kind": "ServiceAccount", "name": "robot",
                      "namespace": "default"}],
        "roleRef": {"kind": "Role", "name": "pod-reader"}}))
    return store


def test_rbac_rule_matching_and_scoping():
    rbac = RBACAuthorizer(_store_with_rbac())
    # Role grants inside its namespace only
    assert rbac.authorize(ALICE, "get", "pods", "default")
    assert rbac.authorize(ALICE, "list", "pods", "default")
    assert not rbac.authorize(ALICE, "create", "pods", "default")
    assert not rbac.authorize(ALICE, "get", "pods", "other")
    assert not rbac.authorize(ALICE, "get", "secrets", "default")
    # ClusterRoleBinding via group: any namespace + cluster scope, any verb
    assert rbac.authorize(ALICE, "delete", "nodes", "")
    assert rbac.authorize(ALICE, "get", "nodes", "anywhere")
    assert not rbac.authorize(BOB, "get", "nodes", "")
    assert not rbac.authorize(BOB, "get", "pods", "default")
    # ServiceAccount subject convention
    assert rbac.authorize(SA, "watch", "pods", "default")
    assert not rbac.authorize(SA, "watch", "pods", "other")


def test_rolebinding_may_reference_clusterrole():
    store = _store_with_rbac()
    store.create(RoleBinding.from_dict({
        "metadata": {"name": "bob-nodes-in-ns", "namespace": "default"},
        "subjects": [{"kind": "User", "name": "bob"}],
        "roleRef": {"kind": "ClusterRole", "name": "node-admin"}}))
    rbac = RBACAuthorizer(store)
    # grants the ClusterRole's rules, but only inside the binding's ns
    assert rbac.authorize(BOB, "get", "nodes", "default")
    assert not rbac.authorize(BOB, "get", "nodes", "")
    assert not rbac.authorize(BOB, "get", "nodes", "other")


def test_union_with_abac():
    store = _store_with_rbac()
    abac = ABACAuthorizer.from_policy_file(
        '{"user": "bob", "resource": "configmaps", "namespace": "default"}')
    union = UnionAuthorizer(abac, RBACAuthorizer(store))
    assert union.authorize(BOB, "get", "configmaps", "default")  # ABAC
    assert union.authorize(ALICE, "get", "pods", "default")      # RBAC
    assert not union.authorize(BOB, "get", "pods", "default")


def _kubectl(url, token, *argv, extra=()):
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO_ROOT)
    return subprocess.run(
        [sys.executable, "-m", "kubernetes_tpu.cli.kubectl",
         "--server", url, "--token", token, *extra, *argv],
        capture_output=True, text=True, timeout=90, env=env)


def test_role_scoped_kubectl_drive():
    """VERDICT done-criterion: a role-scoped user's allowed verbs pass,
    everything else 403s — driven through real kubectl."""
    from http_util import http_store

    store = _store_with_rbac()
    store.create(Pod.from_dict({
        "metadata": {"name": "p1", "namespace": "default"},
        "spec": {"containers": [{"name": "c"}]}}))
    authn = TokenAuthenticator.from_csv(
        "alicetoken,alice,1,\nadmintoken,admin,2,\"system:masters\"\n")
    authz = UnionAuthorizer(
        ABACAuthorizer.from_policy_file(
            '{"group": "system:masters", "resource": "*", '
            '"namespace": "*"}'),
        RBACAuthorizer(store))
    with http_store(store, authenticator=authn,
                    authorizer=authz) as (client, _):
        url = f"http://{client.host}:{client.port}"
        out = _kubectl(url, "alicetoken", "get", "pods")
        assert "p1" in out.stdout, out.stdout + out.stderr
        out = _kubectl(url, "alicetoken", "delete", "pod", "p1")
        assert out.returncode != 0 and "Forbidden" in out.stderr
        out = _kubectl(url, "alicetoken", "get", "secrets")
        assert out.returncode != 0 and "Forbidden" in out.stderr
        # admin via the ABAC leg of the union
        out = _kubectl(url, "admintoken", "delete", "pod", "p1")
        assert "deleted" in out.stdout, out.stdout + out.stderr


@pytest.fixture
def certs(tmp_path):
    crt, key = tmp_path / "tls.crt", tmp_path / "tls.key"
    subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
         "-keyout", str(key), "-out", str(crt), "-days", "1",
         "-subj", "/CN=127.0.0.1",
         "-addext", "subjectAltName=IP:127.0.0.1"],
        check=True, capture_output=True, timeout=60)
    return str(crt), str(key)


def test_tls_serving_end_to_end(certs):
    from http_util import http_store
    from kubernetes_tpu.apiserver.http import RemoteStore

    crt, key = certs
    with http_store(tls_cert_file=crt, tls_key_file=key) as (base, _):
        client = RemoteStore(base.host, base.port, tls=True, ca_file=crt)
        pod = Pod.from_dict({
            "metadata": {"name": "tls-pod"},
            "spec": {"containers": [{"name": "c"}]}})
        client.create(pod)
        assert client.get("Pod", "tls-pod").metadata.name == "tls-pod"
        # kubectl over https with --certificate-authority
        url = f"https://{base.host}:{base.port}"
        out = _kubectl(url, "", "get", "pods",
                       extra=("--certificate-authority", crt))
        assert "tls-pod" in out.stdout, out.stdout + out.stderr
        # plaintext client against the TLS socket fails cleanly
        plain = RemoteStore(base.host, base.port)
        with pytest.raises((ConnectionError, ValueError, OSError)):
            plain.get("Pod", "tls-pod")


def test_resource_names_scope_to_named_requests():
    store = ObjectStore()
    store.create(Role.from_dict({
        "metadata": {"name": "one-secret", "namespace": "default"},
        "rules": [{"resources": ["secrets"], "verbs": ["get"],
                   "resourceNames": ["safe"]}]}))
    store.create(RoleBinding.from_dict({
        "metadata": {"name": "b", "namespace": "default"},
        "subjects": [{"kind": "User", "name": "bob"}],
        "roleRef": {"kind": "Role", "name": "one-secret"}}))
    rbac = RBACAuthorizer(store)
    assert rbac.authorize(BOB, "get", "secrets", "default", "safe")
    assert not rbac.authorize(BOB, "get", "secrets", "default", "other")
    # nameless requests (list) never match a resourceNames-scoped rule
    assert not rbac.authorize(BOB, "list", "secrets", "default")


def test_rbac_group_discovery():
    from http_util import http_store

    with http_store() as (client, _):
        status, body = client.raw("GET", "/apis")
        assert "rbac.authorization.k8s.io" in body
        status, body = client.raw(
            "GET", "/apis/rbac.authorization.k8s.io/v1beta1")
        assert status == 200 and "clusterroles" in body
