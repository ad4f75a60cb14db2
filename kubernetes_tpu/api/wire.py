"""Binary wire codec: the protobuf content-type for hot-path API traffic.

The reference's serializer negotiates `application/vnd.kubernetes.protobuf`
per request (CodecFactory, runtime/serializer/codec_factory.go; the
protobuf serializer at runtime/serializer/protobuf/protobuf.go:75 writes a
4-byte magic prefix + an Unknown envelope holding the typed message bytes).
This module is that codec for the framework's wire: dict payloads in the
v1 camelCase JSON shape (what encode_object/decode_object produce/consume)
encode to/from the wire.proto messages; kinds without a typed message ride
the Unknown envelope as JSON bytes (the runtime.RawExtension escape hatch),
so every payload can negotiate the binary content type.

Generated code is built from wire.proto with the system protoc on first
import (cached in _wiregen/, keyed by a hash of wire.proto and the protoc
command, so a module generated from other sources is rebuilt) and served
by the upb C runtime. If protoc or the protobuf runtime is missing, `available()` is
False and callers stay on JSON — negotiation degrades, nothing breaks.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import subprocess

log = logging.getLogger(__name__)

MAGIC = b"k8s\x00"  # protobuf.go:45 serializer prefix
CONTENT_TYPE = "application/vnd.kubernetes.protobuf"

_pb = None


def _load() -> None:
    global _pb
    here = os.path.dirname(__file__)
    src = os.path.join(here, "wire.proto")
    gen_dir = os.path.join(here, "_wiregen")
    gen = os.path.join(gen_dir, "wire_pb2.py")
    stamp = os.path.join(gen_dir, "wire_pb2.key")
    cmd = ["protoc", f"-I{here}"]
    try:
        with open(src, "rb") as f:
            key = hashlib.sha256(
                f.read() + "\0".join(cmd).encode()).hexdigest()
        try:
            with open(stamp, encoding="utf-8") as f:
                fresh = f.read() == key and os.path.exists(gen)
        except FileNotFoundError:
            fresh = False
        if not fresh:
            os.makedirs(gen_dir, exist_ok=True)
            init = os.path.join(gen_dir, "__init__.py")
            if not os.path.exists(init):
                with open(init, "w", encoding="utf-8"):
                    pass
            # generate into a temp dir + atomic rename: concurrent first
            # importers must never see a half-written module (they would
            # silently degrade to JSON while peers speak protobuf)
            import tempfile
            with tempfile.TemporaryDirectory(dir=here) as tmp:
                subprocess.run([*cmd, f"--python_out={tmp}", src],
                               check=True, capture_output=True, timeout=60)
                os.replace(os.path.join(tmp, "wire_pb2.py"), gen)
                with open(os.path.join(tmp, "key"), "w",
                          encoding="utf-8") as f:
                    f.write(key)
                os.replace(os.path.join(tmp, "key"), stamp)
        from kubernetes_tpu.api._wiregen import wire_pb2
        _pb = wire_pb2
    except (OSError, subprocess.SubprocessError, ImportError) as e:
        log.debug("protobuf wire codec unavailable (%s); JSON only", e)


_load()


def available() -> bool:
    return _pb is not None


# ---- field mapping: v1 JSON dict shape <-> proto messages ----
#
# to_dict() omits empty/default fields and from_dict() defaults them back,
# so the mapping only carries what is present; decoded dicts are
# from_dict-equivalent, not byte-identical JSON.


def _epoch(value) -> float:
    from kubernetes_tpu.api.objects import _cond_time
    return _cond_time(value)


def _meta_to(m, d: dict) -> None:
    m.name = d.get("name", "")
    m.namespace = d.get("namespace", "") or ""
    m.uid = d.get("uid", "") or ""
    for k, v in (d.get("labels") or {}).items():
        m.labels[k] = v
    for k, v in (d.get("annotations") or {}).items():
        m.annotations[k] = v
    m.resource_version = str(d.get("resourceVersion", "") or "")
    if d.get("ownerReferences"):
        m.owner_references_json = json.dumps(d["ownerReferences"]).encode()
    if d.get("creationTimestamp"):
        m.creation_timestamp = _epoch(d["creationTimestamp"])
    if d.get("deletionTimestamp") is not None:
        m.deletion_timestamp = _epoch(d["deletionTimestamp"])
    for f in d.get("finalizers") or []:
        m.finalizers.append(f)


def _meta_from(m) -> dict:
    d: dict = {"name": m.name}
    if m.namespace:
        d["namespace"] = m.namespace
    if m.uid:
        d["uid"] = m.uid
    if m.labels:
        d["labels"] = dict(m.labels)
    if m.annotations:
        d["annotations"] = dict(m.annotations)
    if m.resource_version:
        d["resourceVersion"] = m.resource_version
    if m.owner_references_json:
        d["ownerReferences"] = json.loads(m.owner_references_json)
    if m.creation_timestamp:
        d["creationTimestamp"] = m.creation_timestamp
    if m.HasField("deletion_timestamp"):
        d["deletionTimestamp"] = m.deletion_timestamp
    if m.finalizers:
        d["finalizers"] = list(m.finalizers)
    return d


def _pod_to(msg, d: dict) -> None:
    _meta_to(msg.metadata, d.get("metadata") or {})
    spec = d.get("spec") or {}
    s = msg.spec
    s.node_name = spec.get("nodeName", "") or ""
    for k, v in (spec.get("nodeSelector") or {}).items():
        s.node_selector[k] = v
    for c in spec.get("containers") or []:
        pc = s.containers.add()
        pc.name = c.get("name", "")
        pc.image = c.get("image", "") or ""
        res = c.get("resources") or {}
        for k, v in (res.get("requests") or {}).items():
            pc.requests[k] = str(v)
        for k, v in (res.get("limits") or {}).items():
            pc.limits[k] = str(v)
        for p in c.get("ports") or []:
            pp = pc.ports.add()
            pp.container_port = int(p.get("containerPort", 0))
            pp.host_port = int(p.get("hostPort", 0))
            pp.protocol = p.get("protocol", "") or ""
            pp.host_ip = p.get("hostIP", "") or ""
        if c.get("livenessProbe"):
            pc.liveness_probe_json = json.dumps(c["livenessProbe"]).encode()
        if c.get("readinessProbe"):
            pc.readiness_probe_json = json.dumps(
                c["readinessProbe"]).encode()
    for t in spec.get("tolerations") or []:
        pt = s.tolerations.add()
        pt.key = t.get("key", "") or ""
        pt.operator = t.get("operator", "") or ""
        pt.value = t.get("value", "") or ""
        pt.effect = t.get("effect", "") or ""
        if t.get("tolerationSeconds") is not None:
            pt.toleration_seconds = int(t["tolerationSeconds"])
    if spec.get("affinity"):
        s.affinity_json = json.dumps(spec["affinity"]).encode()
    if spec.get("volumes"):
        s.volumes_json = json.dumps(spec["volumes"]).encode()
    s.scheduler_name = spec.get("schedulerName", "") or ""
    s.restart_policy = spec.get("restartPolicy", "") or ""
    s.priority = int(spec.get("priority", 0) or 0)
    s.service_account_name = spec.get("serviceAccountName", "") or ""
    status = d.get("status") or {}
    msg.status.phase = status.get("phase", "") or ""
    if status.get("conditions"):
        msg.status.conditions_json = json.dumps(
            status["conditions"]).encode()
    msg.status.host_ip = status.get("hostIP", "") or ""
    if status.get("containerStatuses"):
        msg.status.container_statuses_json = json.dumps(
            status["containerStatuses"]).encode()


def _pod_from(msg) -> dict:
    s = msg.spec
    spec: dict = {}
    if s.node_name:
        spec["nodeName"] = s.node_name
    if s.node_selector:
        spec["nodeSelector"] = dict(s.node_selector)
    if s.containers:
        containers = []
        for pc in s.containers:
            c: dict = {"name": pc.name}
            if pc.image:
                c["image"] = pc.image
            res: dict = {}
            if pc.requests:
                res["requests"] = dict(pc.requests)
            if pc.limits:
                res["limits"] = dict(pc.limits)
            if res:
                c["resources"] = res
            if pc.ports:
                c["ports"] = [{
                    "containerPort": pp.container_port,
                    "hostPort": pp.host_port,
                    **({"protocol": pp.protocol} if pp.protocol else {}),
                    **({"hostIP": pp.host_ip} if pp.host_ip else {}),
                } for pp in pc.ports]
            if pc.liveness_probe_json:
                c["livenessProbe"] = json.loads(pc.liveness_probe_json)
            if pc.readiness_probe_json:
                c["readinessProbe"] = json.loads(pc.readiness_probe_json)
            containers.append(c)
        spec["containers"] = containers
    if s.tolerations:
        tolerations = []
        for pt in s.tolerations:
            t: dict = {}
            if pt.key:
                t["key"] = pt.key
            if pt.operator:
                t["operator"] = pt.operator
            if pt.value:
                t["value"] = pt.value
            if pt.effect:
                t["effect"] = pt.effect
            if pt.HasField("toleration_seconds"):
                t["tolerationSeconds"] = pt.toleration_seconds
            tolerations.append(t)
        spec["tolerations"] = tolerations
    if s.affinity_json:
        spec["affinity"] = json.loads(s.affinity_json)
    if s.volumes_json:
        spec["volumes"] = json.loads(s.volumes_json)
    if s.scheduler_name:
        spec["schedulerName"] = s.scheduler_name
    if s.restart_policy:
        spec["restartPolicy"] = s.restart_policy
    if s.priority:
        spec["priority"] = s.priority
    if s.service_account_name:
        spec["serviceAccountName"] = s.service_account_name
    status: dict = {}
    if msg.status.phase:
        status["phase"] = msg.status.phase
    if msg.status.conditions_json:
        status["conditions"] = json.loads(msg.status.conditions_json)
    if msg.status.host_ip:
        status["hostIP"] = msg.status.host_ip
    if msg.status.container_statuses_json:
        status["containerStatuses"] = json.loads(
            msg.status.container_statuses_json)
    return {"kind": "Pod", "apiVersion": "v1",
            "metadata": _meta_from(msg.metadata), "spec": spec,
            "status": status}


def _node_to(msg, d: dict) -> None:
    _meta_to(msg.metadata, d.get("metadata") or {})
    spec = d.get("spec") or {}
    msg.spec.unschedulable = bool(spec.get("unschedulable", False))
    for t in spec.get("taints") or []:
        pt = msg.spec.taints.add()
        pt.key = t.get("key", "") or ""
        pt.value = t.get("value", "") or ""
        pt.effect = t.get("effect", "") or ""
    msg.spec.provider_id = spec.get("providerID", "") or ""
    msg.spec.pod_cidr = spec.get("podCIDR", "") or ""
    status = d.get("status") or {}
    st = msg.status
    for k, v in (status.get("capacity") or {}).items():
        st.capacity[k] = str(v)
    for k, v in (status.get("allocatable") or {}).items():
        st.allocatable[k] = str(v)
    for c in status.get("conditions") or []:
        pc = st.conditions.add()
        pc.type = c.get("type", "") or ""
        pc.status = c.get("status", "") or ""
        pc.last_heartbeat_time = _epoch(c.get("lastHeartbeatTime"))
        pc.last_transition_time = _epoch(c.get("lastTransitionTime"))
        pc.reason = c.get("reason", "") or ""
    if status.get("images"):
        st.images_json = json.dumps(status["images"]).encode()
    if status.get("volumesAttached"):
        st.volumes_attached_json = json.dumps(
            status["volumesAttached"]).encode()
    for v in status.get("volumesInUse") or []:
        st.volumes_in_use.append(v)
    if status.get("daemonEndpoints"):
        st.daemon_endpoints_json = json.dumps(
            status["daemonEndpoints"]).encode()


def _node_from(msg) -> dict:
    spec: dict = {}
    if msg.spec.unschedulable:
        spec["unschedulable"] = True
    if msg.spec.taints:
        spec["taints"] = [{
            "key": t.key,
            **({"value": t.value} if t.value else {}),
            "effect": t.effect} for t in msg.spec.taints]
    if msg.spec.provider_id:
        spec["providerID"] = msg.spec.provider_id
    if msg.spec.pod_cidr:
        spec["podCIDR"] = msg.spec.pod_cidr
    st = msg.status
    status: dict = {}
    if st.capacity:
        status["capacity"] = dict(st.capacity)
    if st.allocatable:
        status["allocatable"] = dict(st.allocatable)
    if st.conditions:
        conditions = []
        for c in st.conditions:
            cd: dict = {"type": c.type, "status": c.status}
            if c.last_heartbeat_time:
                cd["lastHeartbeatTime"] = c.last_heartbeat_time
            if c.last_transition_time:
                cd["lastTransitionTime"] = c.last_transition_time
            if c.reason:
                cd["reason"] = c.reason
            conditions.append(cd)
        status["conditions"] = conditions
    if st.images_json:
        status["images"] = json.loads(st.images_json)
    if st.volumes_attached_json:
        status["volumesAttached"] = json.loads(st.volumes_attached_json)
    if st.volumes_in_use:
        status["volumesInUse"] = list(st.volumes_in_use)
    if st.daemon_endpoints_json:
        status["daemonEndpoints"] = json.loads(st.daemon_endpoints_json)
    return {"kind": "Node", "apiVersion": "v1",
            "metadata": _meta_from(msg.metadata), "spec": spec,
            "status": status}


def _binding_to(msg, d: dict) -> None:
    meta = d.get("metadata") or {}
    msg.name = meta.get("name", "")
    msg.namespace = meta.get("namespace", "") or ""
    msg.target_node = (d.get("target") or {}).get("name", "")


def _binding_from(msg) -> dict:
    return {"kind": "Binding", "apiVersion": "v1",
            "metadata": {"name": msg.name,
                         "namespace": msg.namespace or "default"},
            "target": {"apiVersion": "v1", "kind": "Node",
                       "name": msg.target_node}}


def _event_to(msg, d: dict) -> None:
    _meta_to(msg.metadata, d.get("metadata") or {})
    if d.get("involvedObject"):
        msg.involved_object_json = json.dumps(d["involvedObject"]).encode()
    msg.reason = d.get("reason", "") or ""
    msg.message = d.get("message", "") or ""
    msg.type = d.get("type", "") or ""
    msg.count = int(d.get("count", 1) or 1)
    msg.source_component = (d.get("source") or {}).get("component", "") or ""


def _event_from(msg) -> dict:
    return {"kind": "Event", "apiVersion": "v1",
            "metadata": _meta_from(msg.metadata),
            "involvedObject": (json.loads(msg.involved_object_json)
                               if msg.involved_object_json else {}),
            "reason": msg.reason, "message": msg.message,
            "type": msg.type or "Normal", "count": msg.count or 1,
            "source": {"component": msg.source_component}}


_TYPED = {  # kind -> (message factory name, fill, restore)
    "Pod": ("Pod", _pod_to, _pod_from),
    "Node": ("Node", _node_to, _node_from),
    "Binding": ("Binding", _binding_to, _binding_from),
    "Event": ("Event", _event_to, _event_from),
}


def _encode_unknown(d: dict) -> bytes:
    """One object dict -> Unknown envelope bytes (no magic prefix)."""
    kind = d.get("kind", "")
    u = _pb.Unknown()
    u.kind = kind
    typed = _TYPED.get(kind)
    if typed is not None:
        msg_name, fill, _restore = typed
        msg = getattr(_pb, msg_name)()
        fill(msg, d)
        u.raw = msg.SerializeToString()
    else:
        u.raw = json.dumps(d).encode()
        u.raw_is_json = True
    return u.SerializeToString()


def _decode_unknown(data: bytes) -> dict:
    u = _pb.Unknown()
    u.ParseFromString(data)
    return _restore_unknown(u)


def _restore_unknown(u) -> dict:
    if u.raw_is_json:
        return json.loads(u.raw)
    typed = _TYPED.get(u.kind)
    if typed is None:
        raise ValueError(f"undecodable wire kind {u.kind!r}")
    msg_name, _fill, restore = typed
    msg = getattr(_pb, msg_name)()
    msg.ParseFromString(u.raw)
    return restore(msg)


def encode_payload(payload: dict) -> bytes:
    """Any response/request payload dict -> magic-prefixed wire bytes.
    List payloads ({kind: "XList", items: [...]}) become KList."""
    kind = payload.get("kind", "")
    if kind.endswith("List") and "items" in payload:
        kl = _pb.KList()
        kl.kind = kind
        kl.resource_version = str(
            (payload.get("metadata") or {}).get("resourceVersion", ""))
        for item in payload["items"]:
            kl.items.append(_encode_unknown(item))
        u = _pb.Unknown()
        u.kind = "KList"
        u.raw = kl.SerializeToString()
        return MAGIC + u.SerializeToString()
    return MAGIC + _encode_unknown(payload)


def decode_payload(data: bytes) -> dict:
    """Wire bytes -> payload dict. Raises ValueError on ANY undecodable
    input (protobuf DecodeError is normalized so callers handle one
    exception shape for both content types — json.JSONDecodeError already
    IS a ValueError)."""
    try:
        return _decode_payload(data)
    except ValueError:
        raise
    except Exception as e:  # DecodeError and friends
        raise ValueError(f"undecodable protobuf payload: {e}") from e


def _decode_payload(data: bytes) -> dict:
    if not data.startswith(MAGIC):
        raise ValueError("missing protobuf wire magic")
    u = _pb.Unknown()
    u.ParseFromString(data[len(MAGIC):])
    if u.kind == "KList" and not u.raw_is_json:
        kl = _pb.KList()
        kl.ParseFromString(u.raw)
        return {"kind": kl.kind,
                "metadata": {"resourceVersion": kl.resource_version},
                "items": [_decode_unknown(i) for i in kl.items]}
    return _restore_unknown(u)


# ---- watch framing: 4-byte big-endian length + WatchFrame bytes ----


def encode_watch_frame(event_type: str, resource_version: int,
                       obj_dict: dict) -> bytes:
    f = _pb.WatchFrame()
    f.type = event_type
    f.resource_version = resource_version
    f.object = _encode_unknown(obj_dict)
    body = f.SerializeToString()
    return len(body).to_bytes(4, "big") + body


HEARTBEAT = (0).to_bytes(4, "big")


def decode_watch_frame(body: bytes) -> dict:
    """Frame bytes (after the length prefix) -> the JSON frame shape.
    Raises ValueError on any undecodable input (like decode_payload)."""
    try:
        f = _pb.WatchFrame()
        f.ParseFromString(body)
        return {"type": f.type, "resourceVersion": f.resource_version,
                "object": _decode_unknown(f.object)}
    except ValueError:
        raise
    except Exception as e:
        raise ValueError(f"undecodable watch frame: {e}") from e
