"""Plain reference of psp-thresholds: the sixteen templates under
`templates/` (the gatekeeper-library pod-security-policy rules as this
repo ships them) in straight Python, one function per constraint kind,
each `fn(review, parameters, inventory) -> set of messages`, and
`audit`, the whole audit's answer. Each rule restates its template's
Rego:

  - a field that is absent is Rego's undefined: a body that reads it
    fails, `not` over it holds, and a message that would print it is
    not made;
  - "truthy" is Rego's bare expression: defined and not false;
  - equality is Rego's: a boolean equals only a boolean, and a number
    only a number;
  - numeric ranges compare numbers only.

Violations of one constraint form a set, so equal messages collapse as
Rego's partial sets do. No template here reads `data.inventory`.
"""

from __future__ import annotations

import json
from collections import Counter

from benchmark.refcommon import fmt_v, ident, matches, review_of

_UNDEF = object()


def _get(obj, *path):
    """obj[path...], or _UNDEF where any step is absent."""
    for p in path:
        if not isinstance(obj, dict) or p not in obj:
            return _UNDEF
        obj = obj[p]
    return obj


def _truthy(v) -> bool:
    return v is not _UNDEF and v is not False


def _is_num(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def _eq(a, b) -> bool:
    """Rego's ==: both defined, of one type, and equal."""
    if a is _UNDEF or b is _UNDEF:
        return False
    if isinstance(a, bool) or isinstance(b, bool):
        return isinstance(a, bool) and isinstance(b, bool) and a == b
    if _is_num(a) or _is_num(b):
        return _is_num(a) and _is_num(b) and a == b
    return type(a) is type(b) and a == b


def _items(v) -> list:
    """v[_]: the elements of an array, the values of an object."""
    if isinstance(v, list):
        return v
    if isinstance(v, dict):
        return list(v.values())
    return []


def _key(v) -> str:
    """A value as an element of a Python set standing for a Rego set."""
    return json.dumps(v, sort_keys=True)


def _containers(obj) -> list:
    return [c for c in _items(_get(obj, "spec", "containers"))
            if isinstance(c, dict)]


def _inits(obj) -> list:
    return [c for c in _items(_get(obj, "spec", "initContainers"))
            if isinstance(c, dict)]


def _pod_containers(obj) -> list:
    return _containers(obj) + _inits(obj)


def _name(obj):
    return _get(obj, "metadata", "name")


def _fmt(*values) -> list | None:
    """Each value's %v, or None where one is undefined (the sprintf,
    and so the message, is undefined)."""
    if any(v is _UNDEF for v in values):
        return None
    return [fmt_v(v) for v in values]


def _rank(v) -> tuple:
    """Rego's order of values: null, booleans, numbers, strings, then
    composites (here by their JSON)."""
    if v is None:
        return (0, 0)
    if isinstance(v, bool):
        return (1, v)
    if _is_num(v):
        return (2, v)
    if isinstance(v, str):
        return (3, v)
    return (4, _key(v))


def _fmt_set(values) -> str:
    """A Rego set's %v: its elements in Rego's order, strings quoted."""
    return "{" + ", ".join(fmt_v(v, False)
                           for v in sorted(values, key=_rank)) + "}"


# ------------------------------------- K8sPSPAllowPrivilegeEscalation


def allow_privilege_escalation(review, params, inventory):
    msgs = set()
    for c in _pod_containers(review["object"]):
        ape = _get(c, "securityContext", "allowPrivilegeEscalation")
        if not _truthy(_get(c, "securityContext")) or \
                not _eq(ape, False):
            f = _fmt(_get(c, "name"))
            if f:
                msgs.add(f"Privilege escalation container is not allowed: "
                         f"{f[0]}")
    return msgs


# ---------------------------------------------------- K8sPSPAppArmor


def apparmor(review, params, inventory):
    obj = review["object"]
    allowed = params.get("allowedProfiles", _UNDEF)
    annotations = _get(obj, "metadata", "annotations")
    msgs = set()
    for c in _pod_containers(obj):
        name = _get(c, "name")
        if name is not _UNDEF:
            value = _get(annotations,
                         "container.apparmor.security.beta.kubernetes.io/"
                         + fmt_v(name))
            if any(_eq(value, p) for p in _items(allowed)):
                continue
        f = _fmt(_name(obj), name, allowed)
        if f:
            msgs.add(f"AppArmor profile is not allowed, pod: {f[0]}, "
                     f"container: {f[1]}. Allowed profiles: {f[2]}")
    return msgs


# ------------------------------------------------ K8sPSPCapabilities


def capabilities(review, params, inventory):
    obj = review["object"]
    allowed = {_key(a) for a in _items(params.get("allowedCapabilities"))}
    display = params.get("allowedCapabilities", "NONE")
    drops = params.get("requiredDropCapabilities", _UNDEF)
    want = {_key(d) for d in _items(drops)}
    msgs = set()
    for label, cs in (("container", _containers(obj)),
                      ("init container", _inits(obj))):
        for c in cs:
            caps = _get(c, "securityContext", "capabilities")
            added = {_key(a) for a in _items(_get(caps, "add"))}
            dropped = {_key(d) for d in _items(_get(caps, "drop"))}
            f = _fmt(_get(c, "name"))
            if not f:
                continue
            if _key("*") not in allowed and added - allowed:
                msgs.add(f"{label} <{f[0]}> has a disallowed capability. "
                         f"Allowed capabilities are {fmt_v(display)}")
            if want - dropped:
                msgs.add(f"{label} <{f[0]}> is not dropping all required "
                         f"capabilities. Container must drop all of "
                         f"{fmt_v(drops)}")
    return msgs


# ------------------------------------------------- K8sPSPFlexVolumes


def flex_volumes(review, params, inventory):
    obj = review["object"]
    allowed = params.get("allowedFlexVolumes", _UNDEF)
    msgs = set()
    for v in _items(_get(obj, "spec", "volumes")):
        if not _truthy(_get(v, "flexVolume")):
            continue
        if isinstance(allowed, list) and not allowed:
            continue
        driver = _get(v, "flexVolume", "driver")
        if any(_eq(_get(a, "driver"), driver) for a in _items(allowed)):
            continue
        f = _fmt(v, _name(obj), allowed)
        if f:
            msgs.add(f"FlexVolume {f[0]} is not allowed, pod: {f[1]}. "
                     f"Allowed drivers: {f[2]}")
    return msgs


# -------------------------------------------- K8sPSPForbiddenSysctls


def forbidden_sysctls(review, params, inventory):
    obj = review["object"]
    # {v | v = sysctls[_][name]}: every value of every sysctl entry
    values = {}
    for s in _items(_get(obj, "spec", "securityContext", "sysctls")):
        for v in _items(s):
            values[_key(v)] = v
    if not values:
        return set()
    forbidden = params.get("forbiddenSysctls", _UNDEF)
    banned = _items(forbidden)
    hit = any(_eq(b, "*") for b in banned) or \
        bool(set(values) & {_key(b) for b in banned}) or \
        any(isinstance(v, str) and isinstance(b, str)
            and v.startswith(b.strip("*"))
            for v in values.values() for b in banned)
    if not hit:
        return set()
    f = _fmt(_name(obj), forbidden)
    if not f:
        return set()
    return {f"One of the sysctls {_fmt_set(values.values())} is not "
            f"allowed, pod: {f[0]}. Forbidden sysctls: {f[1]}"}


# ----------------------------------------------------- K8sPSPFSGroup


def _in_some_range(ranges, v) -> bool:
    if not _is_num(v):
        return False
    rs = _items(ranges)
    return len(rs) > 0 and any(
        _is_num(_get(r, "min")) and _is_num(_get(r, "max"))
        and r["min"] <= v <= r["max"] for r in rs)


def fsgroup(review, params, inventory):
    obj = review["object"]
    spec = _get(obj, "spec")
    rule = params.get("rule", _UNDEF)
    ranges = params.get("ranges", _UNDEF)
    group = _get(spec, "securityContext", "fsGroup")
    ok = _eq(rule, "RunAsAny") or \
        (_eq(rule, "MustRunAs") and _in_some_range(ranges, group)) or \
        (_eq(rule, "MayRunAs") and (
            not _truthy(_get(spec, "securityContext"))
            or not _truthy(group) or _in_some_range(ranges, group)))
    if spec is _UNDEF or ok:
        return set()
    f = _fmt(_name(obj), params)
    return {f"The provided pod spec fsGroup is not allowed, pod: {f[0]}. "
            f"Allowed fsGroup: {f[1]}"} if f else set()


# ---------------------------------------------- K8sPSPHostFilesystem


def _path_under(prefix, path) -> bool:
    if not isinstance(prefix, str) or not isinstance(path, str):
        return False
    a = prefix.strip("/").split("/")
    b = path.strip("/").split("/")
    return len(a) <= len(b) and a == b[:len(a)]


def host_filesystem(review, params, inventory):
    obj = review["object"]
    allowed = params.get("allowedHostPaths", _UNDEF)
    mounts = [m for c in _pod_containers(obj)
              for m in _items(_get(c, "volumeMounts"))]
    msgs = set()
    for v in _items(_get(obj, "spec", "volumes")):
        if not _truthy(_get(v, "hostPath")):
            continue
        path = _get(v, "hostPath", "path")
        vname = _get(v, "name")
        writable = vname is not _UNDEF and any(
            _eq(_get(m, "name"), vname) and not _truthy(_get(m, "readOnly"))
            for m in mounts)
        ok = isinstance(allowed, list) and allowed == []
        for e in _items(allowed):
            if not _path_under(_get(e, "pathPrefix"), path):
                continue
            ro = _get(e, "readOnly")
            if not _eq(ro, True) or (_truthy(ro) and not writable):
                ok = True
        if ok:
            continue
        f = _fmt(v, _name(obj), allowed)
        if f:
            msgs.add(f"HostPath volume {f[0]} is not allowed, pod: {f[1]}. "
                     f"Allowed path: {f[2]}")
    return msgs


# ----------------------------------------------- K8sPSPHostNamespace


def host_namespace(review, params, inventory):
    obj = review["object"]
    if not (_truthy(_get(obj, "spec", "hostPID"))
            or _truthy(_get(obj, "spec", "hostIPC"))):
        return set()
    f = _fmt(_name(obj))
    return {f"Sharing the host namespace is not allowed: {f[0]}"} \
        if f else set()


# ----------------------------------------- K8sPSPHostNetworkingPorts


def host_network_ports(review, params, inventory):
    obj = review["object"]
    lo, hi = params.get("min", _UNDEF), params.get("max", _UNDEF)
    ports = [_get(p, "hostPort") for c in _pod_containers(obj)
             for p in _items(_get(c, "ports"))]
    misuse = (not _truthy(params.get("hostNetwork", _UNDEF))
              and _truthy(_get(obj, "spec", "hostNetwork"))) or any(
        _is_num(p) and ((_is_num(lo) and p < lo) or (_is_num(hi) and p > hi))
        for p in ports)
    if not misuse:
        return set()
    f = _fmt(_name(obj), params)
    return {f"The specified hostNetwork and hostPort are not allowed, pod: "
            f"{f[0]}. Allowed values: {f[1]}"} if f else set()


# ------------------------------------------ K8sPSPPrivilegedContainer


def privileged(review, params, inventory):
    msgs = set()
    for c in _pod_containers(review["object"]):
        if not _truthy(_get(c, "securityContext", "privileged")):
            continue
        f = _fmt(_get(c, "name"), _get(c, "securityContext"))
        if f:
            msgs.add(f"Privileged container is not allowed: {f[0]}, "
                     f"securityContext: {f[1]}")
    return msgs


# --------------------------------------------------- K8sPSPProcMount


def proc_mount(review, params, inventory):
    want = params.get("procMount", _UNDEF)
    msgs = set()
    for c in _pod_containers(review["object"]):
        pm = _get(c, "securityContext", "procMount")
        if not _truthy(pm) or _eq(pm, want):
            continue
        f = _fmt(_get(c, "name"), want)
        if f:
            msgs.add(f"ProcMount type is not allowed, container: {f[0]}. "
                     f"Allowed procMount types: {f[1]}")
    return msgs


# -------------------------------------- K8sPSPReadOnlyRootFilesystem


def read_only_root(review, params, inventory):
    msgs = set()
    for c in _pod_containers(review["object"]):
        if _truthy(_get(c, "securityContext")) and _eq(
                _get(c, "securityContext", "readOnlyRootFilesystem"), True):
            continue
        f = _fmt(_get(c, "name"))
        if f:
            msgs.add(f"only read-only root filesystem container is "
                     f"allowed: {f[0]}")
    return msgs


# ----------------------------------------------------- K8sPSPSeccomp

_SECCOMP = "seccomp.security.alpha.kubernetes.io/pod"
_SECCOMP_CONTAINER = "container.seccomp.security.alpha.kubernetes.io"


def seccomp(review, params, inventory):
    obj = review["object"]
    profiles = params.get("allowedProfiles", _UNDEF)
    allowed = _items(profiles)
    annotations = _get(obj, "metadata", "annotations")
    names = [_get(c, "name") for c in _pod_containers(obj)]

    def allows(value) -> bool:
        return any(_eq(value, p) for p in allowed)

    ok = any(_eq(p, "*") for p in allowed) or \
        allows(_get(annotations, _SECCOMP))
    for key, value in (annotations.items()
                       if isinstance(annotations, dict) else ()):
        parts = key.split("/")
        if len(parts) == 2 and parts[0] == _SECCOMP_CONTAINER and \
                any(_eq(parts[1], n) for n in names) and allows(value):
            ok = True
    if ok or _get(obj, "metadata") is _UNDEF:
        return set()
    f = _fmt(_name(obj), profiles)
    return {f"Seccomp profile is not allowed, pod: {f[0]}. Allowed "
            f"profiles: {f[1]}"} if f else set()


# ----------------------------------------------------- K8sPSPSELinux


def selinux(review, params, inventory):
    """The pod's spec bears options always; a container bears them
    where it sets them. A bearer without options is not allowed: the
    template's `not options_allowed(<undefined>)` holds."""
    obj = review["object"]
    allowed = params.get("allowedSELinuxOptions", _UNDEF)
    bearers = [_get(obj, "spec")] + [
        c for c in _pod_containers(obj)
        if _truthy(_get(c, "securityContext", "seLinuxOptions"))]
    for b in bearers:
        if b is _UNDEF:
            continue
        opts = _get(b, "securityContext", "seLinuxOptions")
        if opts is not _UNDEF and any(
                _eq(_get(allowed, k), _get(opts, k))
                for k in ("level", "role", "type", "user")):
            continue
        f = _fmt(_name(obj), allowed)
        return {f"SELinux option is not allowed, pod: {f[0]}. Allowed "
                f"options: {f[1]}"} if f else set()
    return set()


# ------------------------------------------------ K8sPSPAllowedUsers


def _user_ok(rule, uid, params):
    """user_ok(rule, uid): True, False, or None where undefined."""
    if _eq(rule, "RunAsAny"):
        return True
    if _eq(rule, "MustRunAsNonRoot"):
        return not _eq(uid, 0)
    if _eq(rule, "MustRunAs"):
        ranges = _get(params, "runAsUser", "ranges")
        if ranges is _UNDEF:
            return None
        return _is_num(uid) and any(
            _is_num(_get(r, "min")) and _is_num(_get(r, "max"))
            and r["min"] <= uid <= r["max"] for r in _items(ranges))
    return None


def allowed_users(review, params, inventory):
    obj = review["object"]
    rule = _get(params, "runAsUser", "rule")
    if rule is _UNDEF:
        return set()
    msgs = set()
    for c in _pod_containers(obj):
        ctx = _get(c, "securityContext")
        uid = _UNDEF
        if ctx is not _UNDEF:
            uid = _get(ctx, "runAsUser")
            if not _truthy(uid) and review["kind"]["kind"] == "Pod":
                pod_uid = _get(obj, "spec", "securityContext", "runAsUser")
                uid = uid if pod_uid is _UNDEF else pod_uid
        name = _get(c, "name")
        if uid is not _UNDEF and not _user_ok(rule, uid, params):
            f = _fmt(name, uid)
            if f:
                msgs.add(f"Container {f[0]} is attempting to run as "
                         f"disallowed user {f[1]}")
        if not _truthy(uid) and not _eq(rule, "RunAsAny"):
            f = _fmt(name)
            if f:
                msgs.add(f"Container {f[0]} is attempting to run without "
                         f"a required securityContext/runAsUser")
    return msgs


# ------------------------------------------------- K8sPSPVolumeTypes


def volume_types(review, params, inventory):
    obj = review["object"]
    fields = {f for v in _items(_get(obj, "spec", "volumes"))
              if isinstance(v, dict)
              for f, x in v.items() if f != "name" and _truthy(x)}
    allowed = params.get("volumes", _UNDEF)
    names = _items(allowed)
    if any(_eq(t, "*") for t in names) or \
            not fields - {t for t in names if isinstance(t, str)}:
        return set()
    f = _fmt(_name(obj), allowed)
    return {f"One of the volume types {_fmt_set(fields)} is not allowed, "
            f"pod: {f[0]}. Allowed volume types: {f[1]}"} if f else set()


POLICIES = {
    "K8sPSPAllowPrivilegeEscalationContainer": allow_privilege_escalation,
    "K8sPSPAppArmor": apparmor,
    "K8sPSPCapabilities": capabilities,
    "K8sPSPFlexVolumes": flex_volumes,
    "K8sPSPForbiddenSysctls": forbidden_sysctls,
    "K8sPSPFSGroup": fsgroup,
    "K8sPSPHostFilesystem": host_filesystem,
    "K8sPSPHostNamespace": host_namespace,
    "K8sPSPHostNetworkingPorts": host_network_ports,
    "K8sPSPPrivilegedContainer": privileged,
    "K8sPSPProcMount": proc_mount,
    "K8sPSPReadOnlyRootFilesystem": read_only_root,
    "K8sPSPSeccomp": seccomp,
    "K8sPSPSELinux": selinux,
    "K8sPSPAllowedUsers": allowed_users,
    "K8sPSPVolumeTypes": volume_types,
}


def audit(objects: list, constraints: list,
          cap_per_constraint: int | None = None) -> Counter:
    """The audit's answer: a multiset of (object identity, constraint
    name, message), every object judged by every constraint that
    matches it.

    cap_per_constraint: the control's broken guarantee: keep only the
    first n violations of each constraint, in inventory order, as
    Gatekeeper's status cap (--constraint-violations-limit) would."""
    by_kind: dict = {}
    out: Counter = Counter()
    kept: Counter = Counter()
    for obj in objects:
        review = review_of(obj)
        gk = (review["kind"]["group"], review["kind"]["kind"])
        cands = by_kind.get(gk)
        if cands is None:
            cands = by_kind[gk] = [
                c for c in constraints
                if matches((c.get("spec") or {}).get("match") or {}, review)]
        who = ident(obj)
        for c in cands:
            cname = c["metadata"]["name"]
            for m in POLICIES[c["kind"]](
                    review, (c.get("spec") or {}).get("parameters") or {},
                    None):
                if cap_per_constraint is not None:
                    if kept[cname] >= cap_per_constraint:
                        continue
                    kept[cname] += 1
                out[(who, cname, m)] += 1
    return out
