"""KDL → Flow parser tests (analog of parser/tests.rs + model/service.rs tests)."""

import pytest

from fleetflow_tpu.core import (Backend, FlowError, PlacementStrategy, Protocol,
                                RestartPolicy, ServiceType, parse_kdl_string)
from fleetflow_tpu.core.parser import read_kdl_with_includes


class TestServiceParsing:
    def test_basic_service(self):
        flow = parse_kdl_string('''
service "postgres" {
    image "postgres"
    version "16"
    restart "unless-stopped"
    command "postgres -c max_connections=100"
    ports { port host=5432 container=5432 }
    volumes { volume "./data" "/var/lib/postgresql/data" }
    env { POSTGRES_USER "admin"; POSTGRES_DB "app" }
    depends_on "init"
}
''')
        svc = flow.services["postgres"]
        assert svc.image == "postgres"
        assert svc.version == "16"
        assert svc.restart == RestartPolicy.UNLESS_STOPPED
        assert svc.command == "postgres -c max_connections=100"
        assert svc.ports[0].host == 5432
        assert svc.volumes[0].container == "/var/lib/postgresql/data"
        assert svc.environment == {"POSTGRES_USER": "admin", "POSTGRES_DB": "app"}
        assert svc.depends_on == ["init"]
        assert svc.image_name() == "postgres:16"

    def test_image_name_resolution(self):
        # converter.rs:35-46 rules
        flow = parse_kdl_string('''
service "a" { image "repo/app:v3" }
service "b" { image "repo/app"; version "2" }
service "c" { version "1.2" }
service "d" { }
''')
        assert flow.services["a"].image_name() == "repo/app:v3"
        assert flow.services["b"].image_name() == "repo/app:2"
        assert flow.services["c"].image_name() == "c:1.2"
        assert flow.services["d"].image_name() == "d:latest"

    def test_udp_port_and_host_ip(self):
        flow = parse_kdl_string(
            'service "dns" { ports { port host=53 container=53 protocol="udp" host-ip="127.0.0.1" } }')
        p = flow.services["dns"].ports[0]
        assert p.protocol == Protocol.UDP
        assert p.host_ip == "127.0.0.1"
        assert p.key() == ("127.0.0.1", 53, "udp")

    def test_static_service_with_deploy(self):
        flow = parse_kdl_string('''
service "site" {
    type "static"
    build { context "./web"; args { NODE_ENV "production" } }
    deploy "cloudflare-pages" { output "dist"; project "my-site" }
}
''')
        svc = flow.services["site"]
        assert svc.service_type == ServiceType.STATIC
        assert svc.build.context == "./web"
        assert svc.build.args == {"NODE_ENV": "production"}
        assert svc.deploy.type == "cloudflare-pages"
        assert svc.deploy.output == "dist"

    def test_healthcheck_readiness_wait(self):
        flow = parse_kdl_string('''
service "web" {
    healthcheck {
        test "CMD" "curl" "-f" "http://localhost/health"
        interval "10s"
        timeout 5
        retries 5
        start_period "30s"
    }
    readiness { path "/ready"; port 8080; timeout 60; interval 1 }
    wait_for { max_retries 10; initial_delay 2; max_delay 20; multiplier 1.5 }
}
''')
        svc = flow.services["web"]
        assert svc.healthcheck.test[0] == "CMD"
        assert svc.healthcheck.interval == 10.0
        assert svc.healthcheck.retries == 5
        assert svc.readiness.path == "/ready"
        assert svc.readiness.port == 8080
        assert svc.wait.max_retries == 10
        assert svc.wait.delay_for_attempt(0) == 2.0
        assert svc.wait.delay_for_attempt(1) == 3.0
        assert svc.wait.delay_for_attempt(100) == 20.0

    def test_wait_backoff_defaults(self):
        # reference defaults: 23 retries, 1s → 30s cap, x2 (service.rs:337-348)
        flow = parse_kdl_string('service "a" { }')
        from fleetflow_tpu.core.model import WaitConfig
        w = WaitConfig()
        assert w.delay_for_attempt(0) == 1.0
        assert w.delay_for_attempt(1) == 2.0
        assert w.delay_for_attempt(4) == 16.0
        assert w.delay_for_attempt(5) == 30.0  # capped
        assert w.max_retries == 23

    def test_resources(self):
        flow = parse_kdl_string(
            'service "big" { resources { cpu 2.5; memory "4g"; disk "100g" } }')
        r = flow.services["big"].resources
        assert r.cpu == 2.5
        assert r.memory == 4096.0
        assert r.disk == 102400.0

    def test_replicas_and_affinity(self):
        flow = parse_kdl_string('''
service "worker" {
    replicas 3
    anti_affinity "worker"
    colocate_with "cache"
}''')
        svc = flow.services["worker"]
        assert svc.replicas == 3
        assert svc.anti_affinity == ["worker"]
        assert svc.colocate_with == ["cache"]


class TestServiceMerge:
    def test_redefinition_merges(self):
        # parser/mod.rs: service redefinition merges onto existing
        flow = parse_kdl_string('''
service "db" { image "postgres"; version "15"; env { A "1" } }
service "db" { version "16"; env { B "2" } }
''')
        svc = flow.services["db"]
        assert svc.image == "postgres"       # kept (other side None)
        assert svc.version == "16"           # last-wins
        assert svc.environment == {"A": "1", "B": "2"}  # merged

    def test_vec_non_empty_wins(self):
        flow = parse_kdl_string('''
service "db" { ports { port host=1 container=1 } }
service "db" { }
''')
        assert len(flow.services["db"].ports) == 1
        flow2 = parse_kdl_string('''
service "db" { ports { port host=1 container=1 } }
service "db" { ports { port host=2 container=2 } }
''')
        assert [p.host for p in flow2.services["db"].ports] == [2]


class TestStageParsing:
    def test_stage_with_overrides(self):
        flow = parse_kdl_string('''
service "db" { image "surrealdb/surrealdb"; version "v2" }
stage "dev" {
    service "db" {
        ports { port host=50001 container=8000 }
        variables { DEBUG "true" }
    }
}
''')
        st = flow.stages["dev"]
        assert st.services == ["db"]
        resolved = st.resolved_services(flow)[0]
        assert resolved.image == "surrealdb/surrealdb"
        assert resolved.ports[0].host == 50001
        assert resolved.environment["DEBUG"] == "true"

    def test_stage_servers_and_backend(self):
        flow = parse_kdl_string('''
server "cp-1" { }
stage "live" { server "cp-1"; backend "quadlet"; service "x" }
service "x" { }
''')
        st = flow.stages["live"]
        assert st.servers == ["cp-1"]
        assert st.backend == Backend.QUADLET

    def test_stage_redefinition_merges(self):
        flow = parse_kdl_string('''
service "a" { }
service "b" { }
stage "live" { service "a" }
stage "live" { service "b"; variables { K "v" } }
''')
        st = flow.stages["live"]
        assert st.services == ["a", "b"]
        assert st.variables == {"K": "v"}

    def test_unknown_service_in_stage_raises_at_resolve(self):
        flow = parse_kdl_string('stage "s" { service "ghost" }')
        with pytest.raises(KeyError):
            flow.stages["s"].resolved_services(flow)

    def test_placement_policy(self):
        flow = parse_kdl_string('''
stage "live" {
    placement {
        strategy "pack_into_dedicated"
        tier "dedicated"
        required_labels { region "tk1a" }
        preferred_labels { class "compute" }
        quota { cpu 100; memory "512g" }
        spread topology_key="region" max_skew=2
        fallback "preferred_labels" "spread"
    }
}
''')
        p = flow.stages["live"].placement
        assert p.strategy == PlacementStrategy.PACK_INTO_DEDICATED
        assert p.tier == "dedicated"
        assert p.required_labels == {"region": "tk1a"}
        assert p.resource_quota.memory == 512 * 1024
        assert p.spread_constraint.topology_key == "region"
        assert p.spread_constraint.max_skew == 2
        assert p.fallback_policy.relax_order == ["preferred_labels", "spread"]
        assert p.streaming is False

    def test_placement_streaming_flag(self):
        """`streaming #true` marks a stage for deploy.submit; it must
        round-trip the serializer (the CP ships stages as dicts)."""
        from fleetflow_tpu.core.serialize import (stage_from_dict,
                                                  stage_to_dict)
        flow = parse_kdl_string('''
stage "live" {
    placement { streaming #true }
}
''')
        st = flow.stages["live"]
        assert st.placement.streaming is True
        rt = stage_from_dict(stage_to_dict(st))
        assert rt.placement.streaming is True
        # absent by default, and absent from the serialized dict
        flow2 = parse_kdl_string('stage "s" { placement { tier "t" } }')
        d = stage_to_dict(flow2.stages["s"])
        assert "streaming" not in d["placement"]


class TestTopLevel:
    def test_project_provider_server_tenant_registry(self):
        flow = parse_kdl_string('''
project "myproj"
provider "sakura-cloud" { zone "tk1a" }
server "cp" {
    provider "sakura-cloud"
    plan "2core-4gb"
    disk-size 40
    os "debian"
    ssh-key "k1"
    tags "fleetflow:cp"
    capacity { cpu 2; memory "4g"; disk "40g" }
    labels { tier "shared"; region "tk1a"; class "general"; arch "amd64"; custom "x" }
}
variables { GLOBAL_VAR "g" }
registry "ghcr.io/org"
tenant "acme" { display_name "Acme Corp" }
''')
        assert flow.name == "myproj"
        assert flow.providers["sakura-cloud"].zone == "tk1a"
        srv = flow.servers["cp"]
        assert srv.plan == "2core-4gb"
        assert srv.disk_size == 40
        assert srv.capacity.memory == 4096.0
        assert srv.labels.tier == "shared"
        assert srv.labels.as_dict()["class"] == "general"
        assert srv.labels.extra == {"custom": "x"}
        assert flow.variables == {"GLOBAL_VAR": "g"}
        assert flow.registry.url == "ghcr.io/org"
        assert flow.tenant.name == "acme"
        assert flow.tenant.display_name == "Acme Corp"

    def test_unknown_top_level_ignored(self):
        flow = parse_kdl_string('future_thing "x" { }\nproject "p"')
        assert flow.name == "p"


class TestIncludes:
    def test_include_expansion(self, tmp_path):
        (tmp_path / "main.kdl").write_text('project "p"\ninclude "svc.kdl"\n')
        (tmp_path / "svc.kdl").write_text('service "db" { image "postgres" }\n')
        text = read_kdl_with_includes(str(tmp_path / "main.kdl"))
        flow = parse_kdl_string(text)
        assert "db" in flow.services

    def test_include_glob(self, tmp_path):
        (tmp_path / "main.kdl").write_text('include "services/*.kdl"\n')
        (tmp_path / "services").mkdir()
        (tmp_path / "services" / "a.kdl").write_text('service "a" { }\n')
        (tmp_path / "services" / "b.kdl").write_text('service "b" { }\n')
        flow = parse_kdl_string(read_kdl_with_includes(str(tmp_path / "main.kdl")))
        assert set(flow.services) == {"a", "b"}

    def test_include_cycle_detection(self, tmp_path):
        (tmp_path / "a.kdl").write_text('include "b.kdl"\n')
        (tmp_path / "b.kdl").write_text('include "a.kdl"\n')
        with pytest.raises(FlowError, match="cycle"):
            read_kdl_with_includes(str(tmp_path / "a.kdl"))

    def test_include_missing_file(self, tmp_path):
        (tmp_path / "a.kdl").write_text('include "missing.kdl"\n')
        with pytest.raises(FlowError, match="not found"):
            read_kdl_with_includes(str(tmp_path / "a.kdl"))

    def test_unexpanded_include_raises(self):
        with pytest.raises(FlowError, match="include"):
            parse_kdl_string('include "x.kdl"')


class TestReviewRegressions:
    def test_explicit_null_env_value(self):
        flow = parse_kdl_string('service "x" { env { OPT null } }')
        assert flow.services["x"].environment == {"OPT": ""}

    def test_replicas_scale_down_to_one(self):
        flow = parse_kdl_string('''
service "w" { replicas 3 }
service "w" { replicas 1 }
''')
        assert flow.services["w"].replicas == 1

    def test_value_type_annotation(self):
        from fleetflow_tpu.core.kdl import parse_document
        n = parse_document('port (u16)8080')[0]
        assert n.args == [8080]


class TestPortForms:
    def test_compose_string_forms(self):
        flow = parse_kdl_string("""
project "p"
service "a" {
    ports {
        port "8080:80"
        port "9090:90/udp"
        port "127.0.0.1:7070:70"
    }
}
""")
        ports = flow.services["a"].ports
        assert [(p.host, p.container) for p in ports] == [
            (8080, 80), (9090, 90), (7070, 70)]
        assert ports[1].protocol.value == "udp"
        assert ports[2].host_ip == "127.0.0.1"

    def test_bad_port_spec_is_flow_error(self):
        from fleetflow_tpu.core.errors import FlowError
        with pytest.raises(FlowError, match="port"):
            parse_kdl_string(
                'project "p"\nservice "a" { ports { port "a:b:c:d" } }')

    def test_non_numeric_port_is_flow_error(self):
        from fleetflow_tpu.core.errors import FlowError
        with pytest.raises(FlowError):
            parse_kdl_string(
                'project "p"\nservice "a" { ports { port "eighty:80" } }')


def test_kdl_guide_examples_parse_and_mean_something():
    """docs/guide/02-kdl-reference.md's service/stage/provider example
    blocks must parse through the real parser and produce the constructs
    they document — the guide once showed a deploy{strategy} field that
    exists in no model (r5 close review); examples that drift from the
    parser are worse than no examples."""
    import re
    from pathlib import Path

    from fleetflow_tpu.core.parser import parse_kdl_string

    guide = Path(__file__).resolve().parent.parent / (
        "docs/guide/02-kdl-reference.md")
    blocks = re.findall(r"```kdl\n(.*?)```", guide.read_text(), re.S)
    assert len(blocks) >= 4
    # block 1: the full service example; blocks 2-3: stage + infra decls.
    # The top-level block uses literal ellipsis placeholders -> skipped.
    doc = 'project "guide"\n' + blocks[1] + "\n" + blocks[2] + "\n" + blocks[3]
    flow = parse_kdl_string(doc)
    svc = flow.services["api"]
    assert svc.replicas == 3
    assert svc.colocate_with == ["cache"]
    assert svc.anti_affinity == ["db", "color=green"]
    assert svc.anti_affinity_stages == {"color=green": ["sched-0", "sched-1"]}
    assert svc.deploy is not None and svc.deploy.output == "dist"
    assert svc.build is not None and svc.healthcheck is not None
    assert svc.readiness is not None and svc.wait is not None
    stage = flow.stage("live")
    assert stage.placement is not None
    assert stage.placement.spread_constraint is not None
    assert "sakura" in flow.providers and flow.servers


def test_bare_word_false_in_volume_and_build_booleans():
    """bool("false") is True: `read-only false` must parse writable and
    `no-cache false` must keep the cache (same class as the daemon
    config fix; KDL keyword #false already worked)."""
    from fleetflow_tpu.core.parser import parse_kdl_string

    flow = parse_kdl_string("""
project "p"
service "a" {
    image "x"
    volume "/h" "/c" read-only=false
    build { context "."; no-cache false }
}
service "b" {
    image "y"
    volume "/h2" "/c2" read-only=#true
    build { context "."; no-cache #true }
}
""")
    a, b = flow.services["a"], flow.services["b"]
    assert a.volumes[0].read_only is False
    assert a.build.no_cache is False
    assert b.volumes[0].read_only is True
    assert b.build.no_cache is True


def test_deploy_accepts_reference_property_form():
    """The reference's DeployConfig is property-style with a `provider`
    key (service.rs:129-141): `deploy provider="cloudflare-pages"
    output="dist" project="site"` must port over unchanged; our
    child-node `type` spelling keeps working."""
    from fleetflow_tpu.core.parser import parse_kdl_string

    flow = parse_kdl_string("""
project "p"
service "site" {
    type "static"
    image "none"
    deploy provider="cloudflare-pages" output="dist" project="shop-site"
}
service "site2" {
    type "static"
    image "none"
    deploy { provider "s3"; output "build" }
}
""")
    d = flow.services["site"].deploy
    assert (d.type, d.output, d.project) == ("cloudflare-pages", "dist",
                                             "shop-site")
    d2 = flow.services["site2"].deploy
    assert (d2.type, d2.output) == ("s3", "build")


def test_health_readiness_wait_accept_reference_property_form():
    """The reference declares these property-style (service.rs:236-330);
    dropping the properties silently kept defaults — a ported config's
    health tuning vanished without a word."""
    from fleetflow_tpu.core.parser import parse_kdl_string

    flow = parse_kdl_string("""
project "p"
service "api" {
    image "x"
    healthcheck test="curl -f localhost" interval=15 timeout=5 retries=4 start-period=20
    readiness path="/healthz" port=9090 timeout=10 interval=1
    wait max-retries=10 initial-delay=2 max-delay=20 multiplier=1.5
}
""")
    svc = flow.services["api"]
    h = svc.healthcheck
    assert (h.test, h.interval, h.timeout, h.retries, h.start_period) == (
        ["curl -f localhost"], 15.0, 5.0, 4, 20.0)
    r = svc.readiness
    assert (r.path, r.port, r.timeout, r.interval) == ("/healthz", 9090,
                                                       10.0, 1.0)
    w = svc.wait
    assert (w.max_retries, w.initial_delay, w.max_delay, w.multiplier) == (
        10, 2.0, 20.0, 1.5)


def test_provider_and_server_accept_reference_property_form():
    """The reference declares infra property-style (cloud.rs:10-69):
    provider zone= and server provider=/plan=/disk-size=/... — dropping
    the properties silently lost the whole server inventory of a ported
    config."""
    from fleetflow_tpu.core.parser import parse_kdl_string

    flow = parse_kdl_string("""
project "p"
provider "sakura" zone="tk1a" api-token="t"
server "web-1" provider="sakura" plan="2core-4gb" disk-size=40 os="ubuntu" \
archive="gold" ssh-host="10.0.0.1" ssh-user="ops" ssh-key="deploy" \
startup-script="init" dns-hostname="web-1.example"
""")
    pr = flow.providers["sakura"]
    assert pr.zone == "tk1a" and pr.options.get("api-token") == "t"
    sv = flow.servers["web-1"]
    assert (sv.provider, sv.plan, sv.disk_size, sv.os) == (
        "sakura", "2core-4gb", 40, "ubuntu")
    assert (sv.archive, sv.ssh_host, sv.ssh_user) == ("gold", "10.0.0.1",
                                                      "ops")
    assert sv.ssh_keys == ["deploy"]
    assert sv.startup_script == "init" and sv.dns_hostname == "web-1.example"
