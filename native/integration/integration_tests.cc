// C++ integration suite against the LIVE native front-end — the role of
// the reference's typed dual-protocol client tests + soak tests
// (reference src/c++/tests/cc_client_test.cc:2173-2184 runs every case
// for both InferenceServerGrpcClient and InferenceServerHttpClient;
// memory_leak_test.cc and client_timeout_test.cc cover the soak and
// deadline behaviors).
//
// The binary spawns `python -m client_tpu.server` (hermetic CPU env),
// reads the ports from its `server_started` event, and drives BOTH C++
// clients through a uniform Driver adapter, so every dual-protocol case
// asserts identical semantics over gRPC and HTTP — exactly the
// asymmetries example smoke runs don't catch.
#include <csignal>
#include <poll.h>
#include <sys/types.h>
#include <sys/wait.h>
#include <unistd.h>

#include <atomic>
#include <chrono>
#include <condition_variable>
#include <cstring>
#include <fstream>
#include <memory>
#include <mutex>
#include <sstream>
#include <string>
#include <thread>
#include <vector>

#include "../tests/test_framework.h"
#include "client_tpu/grpc/_generated/grpc_service.pb.h"
#include "common.h"
#include "grpc_client.h"
#include "http_client.h"
#include "json.h"
#include "shm_utils.h"

using namespace ctpu;

#ifndef CTPU_REPO_ROOT
#error "CTPU_REPO_ROOT must be defined by the build"
#endif

namespace {

// -- live server fixture -----------------------------------------------------

struct ServerProcess {
  pid_t pid = -1;
  int http_port = 0;
  int grpc_port = 0;
  std::thread drainer;
  FILE* out = nullptr;

  // One log line: sets the ports when it is the server_started event.
  void ParseServerStarted(const std::string& line) {
    if (line.find("server_started") == std::string::npos) return;
    try {
      const json::Value event = json::Parse(line);
      if (event["event"].AsString() != "server_started") return;
      http_port = static_cast<int>(event["http_port"].AsInt());
      grpc_port = static_cast<int>(event["grpc_port"].AsInt());
    } catch (const std::exception&) {
      // not a JSON line (a library's stray notice): keep waiting
    }
  }

  bool Start() {
    int pipefd[2];
    if (pipe(pipefd) != 0) return false;
    pid = fork();
    if (pid == 0) {
      dup2(pipefd[1], 1);
      dup2(pipefd[1], 2);
      close(pipefd[0]);
      close(pipefd[1]);
      // Hermetic child env (client_tpu.testing.hermetic_child_env role):
      // host JAX backend.
      setenv("JAX_PLATFORMS", "cpu", 1);
      const char* existing = getenv("PYTHONPATH");
      std::string pythonpath = CTPU_REPO_ROOT;
      if (existing != nullptr && existing[0] != '\0') {
        pythonpath += std::string(":") + existing;
      }
      setenv("PYTHONPATH", pythonpath.c_str(), 1);
      execlp("python", "python", "-m", "client_tpu.server", "--host",
             "127.0.0.1", "--http-port", "0", "--grpc-port", "0",
             static_cast<char*>(nullptr));
      _exit(127);
    }
    close(pipefd[1]);
    // Wait, up to a deadline, for the `server_started` lifecycle event
    // (one JSON line from the structured logger) and read the bound
    // ports from it. Model warmup can take a while; a server that never
    // announces itself is a failed start, not a hang.
    const auto deadline =
        std::chrono::steady_clock::now() + std::chrono::seconds(180);
    std::string pending;
    char chunk[4096];
    while (http_port == 0 || grpc_port == 0) {
      const auto left = std::chrono::duration_cast<std::chrono::milliseconds>(
          deadline - std::chrono::steady_clock::now());
      if (left.count() <= 0) break;
      struct pollfd pfd = {pipefd[0], POLLIN, 0};
      if (poll(&pfd, 1, static_cast<int>(left.count())) <= 0) break;
      const ssize_t n = read(pipefd[0], chunk, sizeof(chunk));
      if (n <= 0) break;  // server exited before announcing itself
      pending.append(chunk, static_cast<size_t>(n));
      size_t eol;
      while ((eol = pending.find('\n')) != std::string::npos) {
        ParseServerStarted(pending.substr(0, eol));
        pending.erase(0, eol + 1);
      }
    }
    if (http_port == 0 || grpc_port == 0) {
      close(pipefd[0]);
      Stop();
      return false;
    }
    out = fdopen(pipefd[0], "r");
    if (out == nullptr) return false;
    // Keep draining server logs so a full pipe can never block it.
    drainer = std::thread([this] {
      char buf[4096];
      while (fgets(buf, sizeof(buf), out) != nullptr) {
      }
    });
    return true;
  }

  void Stop() {
    if (pid > 0) {
      kill(pid, SIGTERM);
      int status = 0;
      waitpid(pid, &status, 0);
      pid = -1;
    }
    if (drainer.joinable()) drainer.join();
    if (out != nullptr) {
      fclose(out);
      out = nullptr;
    }
  }
};

ServerProcess& Server() {
  static ServerProcess* server = new ServerProcess();
  return *server;
}

// -- uniform dual-protocol driver -------------------------------------------

struct Driver {
  virtual ~Driver() = default;
  virtual const char* name() const = 0;
  virtual Error Live(bool* live) = 0;
  virtual Error Ready(bool* ready) = 0;
  virtual Error ModelReady(const std::string& model, bool* ready) = 0;
  virtual Error MetadataIO(const std::string& model,
                           std::vector<std::string>* inputs,
                           std::vector<std::string>* outputs) = 0;
  virtual Error MaxBatchSize(const std::string& model, int64_t* mbs) = 0;
  virtual Error IndexNames(std::vector<std::string>* names) = 0;
  virtual Error Infer(const InferOptions& options,
                      const std::vector<InferInput*>& inputs,
                      const std::vector<const InferRequestedOutput*>& outputs,
                      std::unique_ptr<InferResult>* result) = 0;
  virtual Error RegisterShm(const std::string& name, const std::string& key,
                            size_t byte_size) = 0;
  virtual Error UnregisterShm(const std::string& name) = 0;
  virtual Error StatsSuccessCount(const std::string& model,
                                  uint64_t* count) = 0;
  virtual Error UpdateTraceLevel(const std::string& level) = 0;
  virtual Error Load(const std::string& model) = 0;
  virtual Error Unload(const std::string& model) = 0;
};

struct GrpcDriver : Driver {
  std::unique_ptr<InferenceServerGrpcClient> client;

  GrpcDriver() {
    InferenceServerGrpcClient::Create(
        &client, "127.0.0.1:" + std::to_string(Server().grpc_port));
  }
  const char* name() const override { return "grpc"; }
  Error Live(bool* live) override { return client->IsServerLive(live); }
  Error Ready(bool* ready) override { return client->IsServerReady(ready); }
  Error ModelReady(const std::string& model, bool* ready) override {
    return client->IsModelReady(ready, model);
  }
  Error MetadataIO(const std::string& model, std::vector<std::string>* ins,
                   std::vector<std::string>* outs) override {
    inference::ModelMetadataResponse metadata;
    CTPU_RETURN_IF_ERROR(client->ModelMetadata(&metadata, model));
    for (const auto& t : metadata.inputs()) ins->push_back(t.name());
    for (const auto& t : metadata.outputs()) outs->push_back(t.name());
    return Error::Success();
  }
  Error MaxBatchSize(const std::string& model, int64_t* mbs) override {
    inference::ModelConfigResponse config;
    CTPU_RETURN_IF_ERROR(client->ModelConfig(&config, model));
    *mbs = config.config().max_batch_size();
    return Error::Success();
  }
  Error IndexNames(std::vector<std::string>* names) override {
    inference::RepositoryIndexResponse index;
    CTPU_RETURN_IF_ERROR(client->ModelRepositoryIndex(&index));
    for (const auto& m : index.models()) names->push_back(m.name());
    return Error::Success();
  }
  Error Infer(const InferOptions& options,
              const std::vector<InferInput*>& inputs,
              const std::vector<const InferRequestedOutput*>& outputs,
              std::unique_ptr<InferResult>* result) override {
    InferResult* raw = nullptr;
    Error err = client->Infer(&raw, options, inputs, outputs);
    result->reset(raw);
    return err;
  }
  Error RegisterShm(const std::string& name, const std::string& key,
                    size_t byte_size) override {
    return client->RegisterSystemSharedMemory(name, key, byte_size);
  }
  Error UnregisterShm(const std::string& name) override {
    return client->UnregisterSystemSharedMemory(name);
  }
  Error StatsSuccessCount(const std::string& model,
                          uint64_t* count) override {
    inference::ModelStatisticsResponse stats;
    CTPU_RETURN_IF_ERROR(client->ModelInferenceStatistics(&stats, model));
    for (const auto& ms : stats.model_stats()) {
      if (ms.name() == model) {
        *count = ms.inference_stats().success().count();
        return Error::Success();
      }
    }
    return Error("model not in statistics response");
  }
  Error UpdateTraceLevel(const std::string& level) override {
    inference::TraceSettingResponse response;
    return client->UpdateTraceSettings(&response, "",
                                       {{"trace_level", {level}}});
  }
  Error Load(const std::string& model) override {
    return client->LoadModel(model);
  }
  Error Unload(const std::string& model) override {
    return client->UnloadModel(model);
  }
};

struct HttpDriver : Driver {
  std::unique_ptr<InferenceServerHttpClient> client;

  HttpDriver() {
    InferenceServerHttpClient::Create(
        &client, "127.0.0.1:" + std::to_string(Server().http_port));
  }
  const char* name() const override { return "http"; }
  Error Live(bool* live) override { return client->IsServerLive(live); }
  Error Ready(bool* ready) override { return client->IsServerReady(ready); }
  Error ModelReady(const std::string& model, bool* ready) override {
    return client->IsModelReady(ready, model);
  }
  Error MetadataIO(const std::string& model, std::vector<std::string>* ins,
                   std::vector<std::string>* outs) override {
    json::Value metadata;
    CTPU_RETURN_IF_ERROR(client->ModelMetadata(&metadata, model));
    for (const auto& t : metadata.AsObject().at("inputs").AsArray()) {
      ins->push_back(t.AsObject().at("name").AsString());
    }
    for (const auto& t : metadata.AsObject().at("outputs").AsArray()) {
      outs->push_back(t.AsObject().at("name").AsString());
    }
    return Error::Success();
  }
  Error MaxBatchSize(const std::string& model, int64_t* mbs) override {
    json::Value config;
    CTPU_RETURN_IF_ERROR(client->ModelConfig(&config, model));
    *mbs = config.AsObject().at("max_batch_size").AsInt();
    return Error::Success();
  }
  Error IndexNames(std::vector<std::string>* names) override {
    json::Value index;
    CTPU_RETURN_IF_ERROR(client->ModelRepositoryIndex(&index));
    for (const auto& m : index.AsArray()) {
      names->push_back(m.AsObject().at("name").AsString());
    }
    return Error::Success();
  }
  Error Infer(const InferOptions& options,
              const std::vector<InferInput*>& inputs,
              const std::vector<const InferRequestedOutput*>& outputs,
              std::unique_ptr<InferResult>* result) override {
    return client->Infer(result, options, inputs, outputs);
  }
  Error RegisterShm(const std::string& name, const std::string& key,
                    size_t byte_size) override {
    return client->RegisterSystemSharedMemory(name, key, byte_size);
  }
  Error UnregisterShm(const std::string& name) override {
    return client->UnregisterSystemSharedMemory(name);
  }
  Error StatsSuccessCount(const std::string& model,
                          uint64_t* count) override {
    json::Value stats;
    CTPU_RETURN_IF_ERROR(client->ModelInferenceStatistics(&stats, model));
    for (const auto& ms : stats.AsObject().at("model_stats").AsArray()) {
      if (ms.AsObject().at("name").AsString() == model) {
        *count = static_cast<uint64_t>(ms.AsObject()
                                           .at("inference_stats")
                                           .AsObject()
                                           .at("success")
                                           .AsObject()
                                           .at("count")
                                           .AsInt());
        return Error::Success();
      }
    }
    return Error("model not in statistics response");
  }
  Error UpdateTraceLevel(const std::string& level) override {
    json::Value response;
    return client->UpdateTraceSettings(&response, "",
                                       {{"trace_level", {level}}});
  }
  Error Load(const std::string& model) override {
    return client->LoadModel(model);
  }
  Error Unload(const std::string& model) override {
    return client->UnloadModel(model);
  }
};

// Per-case fresh drivers: cases must not leak state into each other
// through a shared connection (and connection reuse is itself covered by
// the soak cases).
std::vector<std::unique_ptr<Driver>> MakeDrivers() {
  std::vector<std::unique_ptr<Driver>> drivers;
  drivers.emplace_back(new GrpcDriver());
  drivers.emplace_back(new HttpDriver());
  return drivers;
}

// add_sub request helpers -----------------------------------------------------

std::vector<int32_t> Iota(size_t n, int32_t start = 0) {
  std::vector<int32_t> v(n);
  for (size_t i = 0; i < n; ++i) v[i] = start + static_cast<int32_t>(i);
  return v;
}

struct SimpleRequest {
  std::vector<int32_t> in0 = Iota(16);
  std::vector<int32_t> in1 = std::vector<int32_t>(16, 1);
  InferInput input0{"INPUT0", {1, 16}, "INT32"};
  InferInput input1{"INPUT1", {1, 16}, "INT32"};

  SimpleRequest() {
    input0.AppendRaw(reinterpret_cast<uint8_t*>(in0.data()),
                     in0.size() * sizeof(int32_t));
    input1.AppendRaw(reinterpret_cast<uint8_t*>(in1.data()),
                     in1.size() * sizeof(int32_t));
  }
  std::vector<InferInput*> inputs() { return {&input0, &input1}; }
};

void CheckSimpleResult(InferResult* result) {
  const uint8_t* buf = nullptr;
  size_t byte_size = 0;
  CHECK_OK(result->RawData("OUTPUT0", &buf, &byte_size));
  REQUIRE(byte_size == 16 * sizeof(int32_t));
  const int32_t* add = reinterpret_cast<const int32_t*>(buf);
  for (int i = 0; i < 16; ++i) CHECK_EQ(add[i], i + 1);
  CHECK_OK(result->RawData("OUTPUT1", &buf, &byte_size));
  REQUIRE(byte_size == 16 * sizeof(int32_t));
  const int32_t* sub = reinterpret_cast<const int32_t*>(buf);
  for (int i = 0; i < 16; ++i) CHECK_EQ(sub[i], i - 1);
}

size_t RssKb() {
  std::ifstream status("/proc/self/status");
  std::string line;
  while (std::getline(status, line)) {
    if (line.rfind("VmRSS:", 0) == 0) {
      return std::strtoul(line.c_str() + 6, nullptr, 10);
    }
  }
  return 0;
}

}  // namespace

// -- health & metadata (dual-protocol) ---------------------------------------

TEST_CASE("integration: server live and ready on both protocols") {
  for (auto& d : MakeDrivers()) {
    bool live = false;
    bool ready = false;
    CHECK_OK(d->Live(&live));
    CHECK_OK(d->Ready(&ready));
    CHECK(live);
    CHECK(ready);
  }
}

TEST_CASE("integration: model ready") {
  for (auto& d : MakeDrivers()) {
    bool ready = false;
    CHECK_OK(d->ModelReady("simple", &ready));
    CHECK(ready);
    bool missing_ready = true;
    // Unknown model: either a clean error or ready=false, never true.
    Error err = d->ModelReady("no_such_model", &missing_ready);
    CHECK((!err.IsOk() || !missing_ready));
  }
}

TEST_CASE("integration: model metadata io names agree across protocols") {
  std::vector<std::vector<std::string>> all_inputs;
  for (auto& d : MakeDrivers()) {
    std::vector<std::string> inputs;
    std::vector<std::string> outputs;
    CHECK_OK(d->MetadataIO("simple", &inputs, &outputs));
    CHECK_EQ(inputs.size(), 2u);
    CHECK_EQ(outputs.size(), 2u);
    all_inputs.push_back(inputs);
  }
  REQUIRE(all_inputs.size() == 2);
  CHECK(all_inputs[0] == all_inputs[1]);
}

TEST_CASE("integration: model config max_batch_size") {
  for (auto& d : MakeDrivers()) {
    int64_t mbs = 0;
    CHECK_OK(d->MaxBatchSize("simple", &mbs));
    CHECK_EQ(mbs, 64);
  }
}

TEST_CASE("integration: repository index lists the fixture models") {
  for (auto& d : MakeDrivers()) {
    std::vector<std::string> names;
    CHECK_OK(d->IndexNames(&names));
    auto has = [&](const char* n) {
      for (const auto& name : names) {
        if (name == n) return true;
      }
      return false;
    };
    CHECK(has("simple"));
    CHECK(has("identity_fp32"));
    CHECK(has("identity_bytes"));
  }
}

// -- inference (dual-protocol) -----------------------------------------------

TEST_CASE("integration: add_sub inference is correct on both protocols") {
  for (auto& d : MakeDrivers()) {
    SimpleRequest req;
    InferOptions options("simple");
    std::unique_ptr<InferResult> result;
    CHECK_OK(d->Infer(options, req.inputs(), {}, &result));
    REQUIRE(result != nullptr);
    CheckSimpleResult(result.get());
  }
}

TEST_CASE("integration: request id is echoed") {
  for (auto& d : MakeDrivers()) {
    SimpleRequest req;
    InferOptions options("simple");
    options.request_id = std::string("it-") + d->name();
    std::unique_ptr<InferResult> result;
    CHECK_OK(d->Infer(options, req.inputs(), {}, &result));
    REQUIRE(result != nullptr);
    std::string id;
    CHECK_OK(result->Id(&id));
    CHECK_EQ(id, options.request_id);
  }
}

TEST_CASE("integration: model name and version in the response") {
  for (auto& d : MakeDrivers()) {
    SimpleRequest req;
    std::unique_ptr<InferResult> result;
    CHECK_OK(d->Infer(InferOptions("simple"), req.inputs(), {}, &result));
    REQUIRE(result != nullptr);
    std::string name;
    CHECK_OK(result->ModelName(&name));
    CHECK_EQ(name, "simple");
  }
}

TEST_CASE("integration: unknown model fails cleanly") {
  for (auto& d : MakeDrivers()) {
    SimpleRequest req;
    std::unique_ptr<InferResult> result;
    Error err = d->Infer(InferOptions("no_such_model"), req.inputs(), {},
                         &result);
    bool failed = !err.IsOk() ||
                  (result != nullptr && !result->RequestStatus().IsOk());
    CHECK(failed);
  }
}

TEST_CASE("integration: wrong payload size fails cleanly") {
  for (auto& d : MakeDrivers()) {
    std::vector<int32_t> half = Iota(8);
    InferInput input0("INPUT0", {1, 16}, "INT32");  // claims 16 elements
    input0.AppendRaw(reinterpret_cast<uint8_t*>(half.data()),
                     half.size() * sizeof(int32_t));
    SimpleRequest req;
    std::unique_ptr<InferResult> result;
    Error err = d->Infer(InferOptions("simple"), {&input0, &req.input1}, {},
                         &result);
    bool failed = !err.IsOk() ||
                  (result != nullptr && !result->RequestStatus().IsOk());
    CHECK(failed);
  }
}

TEST_CASE("integration: missing input fails cleanly") {
  for (auto& d : MakeDrivers()) {
    SimpleRequest req;
    std::unique_ptr<InferResult> result;
    Error err =
        d->Infer(InferOptions("simple"), {&req.input0}, {}, &result);
    bool failed = !err.IsOk() ||
                  (result != nullptr && !result->RequestStatus().IsOk());
    CHECK(failed);
  }
}

TEST_CASE("integration: batched request (batch 8)") {
  for (auto& d : MakeDrivers()) {
    std::vector<int32_t> in0 = Iota(8 * 16);
    std::vector<int32_t> in1(8 * 16, 2);
    InferInput input0("INPUT0", {8, 16}, "INT32");
    InferInput input1("INPUT1", {8, 16}, "INT32");
    input0.AppendRaw(reinterpret_cast<uint8_t*>(in0.data()),
                     in0.size() * sizeof(int32_t));
    input1.AppendRaw(reinterpret_cast<uint8_t*>(in1.data()),
                     in1.size() * sizeof(int32_t));
    std::unique_ptr<InferResult> result;
    CHECK_OK(d->Infer(InferOptions("simple"), {&input0, &input1}, {},
                      &result));
    REQUIRE(result != nullptr);
    const uint8_t* buf = nullptr;
    size_t byte_size = 0;
    CHECK_OK(result->RawData("OUTPUT0", &buf, &byte_size));
    REQUIRE(byte_size == 8 * 16 * sizeof(int32_t));
    const int32_t* add = reinterpret_cast<const int32_t*>(buf);
    for (int i = 0; i < 8 * 16; ++i) CHECK_EQ(add[i], i + 2);
  }
}

TEST_CASE("integration: requested-output subset returns only that output") {
  for (auto& d : MakeDrivers()) {
    SimpleRequest req;
    InferRequestedOutput only0("OUTPUT0");
    std::unique_ptr<InferResult> result;
    CHECK_OK(d->Infer(InferOptions("simple"), req.inputs(), {&only0},
                      &result));
    REQUIRE(result != nullptr);
    const uint8_t* buf = nullptr;
    size_t byte_size = 0;
    CHECK_OK(result->RawData("OUTPUT0", &buf, &byte_size));
    CHECK_EQ(byte_size, 16 * sizeof(int32_t));
    Error err = result->RawData("OUTPUT1", &buf, &byte_size);
    CHECK(!err.IsOk());
  }
}

TEST_CASE("integration: classification extension returns labeled strings") {
  for (auto& d : MakeDrivers()) {
    SimpleRequest req;
    InferRequestedOutput top2("OUTPUT0", /*class_count=*/2);
    std::unique_ptr<InferResult> result;
    CHECK_OK(d->Infer(InferOptions("simple"), req.inputs(), {&top2},
                      &result));
    REQUIRE(result != nullptr);
    std::vector<std::string> entries;
    CHECK_OK(result->StringData("OUTPUT0", &entries));
    REQUIRE(entries.size() == 2);
    // "value:index" — top-1 of INPUT0+INPUT1 = 16 at index 15
    CHECK(entries[0].find(":15") != std::string::npos);
  }
}

TEST_CASE("integration: BYTES tensors roundtrip through identity_bytes") {
  for (auto& d : MakeDrivers()) {
    InferInput input("INPUT0", {1, 2}, "BYTES");
    CHECK_OK(input.AppendFromString({"hello", "tpu-world"}));
    std::unique_ptr<InferResult> result;
    CHECK_OK(d->Infer(InferOptions("identity_bytes"), {&input}, {},
                      &result));
    REQUIRE(result != nullptr);
    std::vector<std::string> out;
    CHECK_OK(result->StringData("OUTPUT0", &out));
    REQUIRE(out.size() == 2);
    CHECK_EQ(out[0], "hello");
    CHECK_EQ(out[1], "tpu-world");
  }
}

// -- InferMulti + async ------------------------------------------------------

TEST_CASE("integration: grpc InferMulti runs each request") {
  GrpcDriver driver;
  SimpleRequest req;
  std::vector<InferOptions> options{InferOptions("simple")};
  std::vector<std::vector<InferInput*>> inputs{
      req.inputs(), req.inputs(), req.inputs()};
  std::vector<InferResult*> results;
  CHECK_OK(driver.client->InferMulti(&results, options, inputs));
  REQUIRE(results.size() == 3);
  for (InferResult* raw : results) {
    std::unique_ptr<InferResult> result(raw);
    CHECK_OK(result->RequestStatus());
    CheckSimpleResult(result.get());
  }
}

TEST_CASE("integration: grpc AsyncInfer delivers on a callback thread") {
  GrpcDriver driver;
  SimpleRequest req;
  std::mutex mu;
  std::condition_variable cv;
  std::unique_ptr<InferResult> result;
  bool done = false;
  CHECK_OK(driver.client->AsyncInfer(
      [&](InferResult* raw) {
        std::lock_guard<std::mutex> lk(mu);
        result.reset(raw);
        done = true;
        cv.notify_all();
      },
      InferOptions("simple"), req.inputs()));
  std::unique_lock<std::mutex> lk(mu);
  REQUIRE(cv.wait_for(lk, std::chrono::seconds(30), [&] { return done; }));
  REQUIRE(result != nullptr);
  CHECK_OK(result->RequestStatus());
  CheckSimpleResult(result.get());
}

TEST_CASE("integration: http AsyncInfer delivers on a callback thread") {
  HttpDriver driver;
  SimpleRequest req;
  std::mutex mu;
  std::condition_variable cv;
  std::unique_ptr<InferResult> result;
  bool done = false;
  CHECK_OK(driver.client->AsyncInfer(
      [&](InferResult* raw) {
        std::lock_guard<std::mutex> lk(mu);
        result.reset(raw);
        done = true;
        cv.notify_all();
      },
      InferOptions("simple"), req.inputs()));
  std::unique_lock<std::mutex> lk(mu);
  REQUIRE(cv.wait_for(lk, std::chrono::seconds(30), [&] { return done; }));
  REQUIRE(result != nullptr);
  CHECK_OK(result->RequestStatus());
  CheckSimpleResult(result.get());
}

// -- shared memory ------------------------------------------------------------

TEST_CASE("integration: system shm input region drives inference") {
  for (auto& d : MakeDrivers()) {
    const std::string key =
        std::string("/it_shm_in_") + d->name() + std::to_string(getpid());
    int fd = -1;
    CHECK_OK(CreateSharedMemoryRegion(key, 64, &fd));
    void* addr = nullptr;
    CHECK_OK(MapSharedMemory(fd, 0, 64, &addr));
    std::vector<int32_t> in0 = Iota(16);
    memcpy(addr, in0.data(), 64);
    CHECK_OK(d->RegisterShm("it_in", key, 64));

    InferInput input0("INPUT0", {1, 16}, "INT32");
    CHECK_OK(input0.SetSharedMemory("it_in", 64));
    SimpleRequest req;
    std::unique_ptr<InferResult> result;
    CHECK_OK(d->Infer(InferOptions("simple"), {&input0, &req.input1}, {},
                      &result));
    REQUIRE(result != nullptr);
    CheckSimpleResult(result.get());

    CHECK_OK(d->UnregisterShm("it_in"));
    CHECK_OK(UnmapSharedMemory(addr, 64));
    CHECK_OK(CloseSharedMemory(fd));
    CHECK_OK(UnlinkSharedMemoryRegion(key));
  }
}

TEST_CASE("integration: shm output redirect returns region refs") {
  for (auto& d : MakeDrivers()) {
    const std::string key =
        std::string("/it_shm_out_") + d->name() + std::to_string(getpid());
    int fd = -1;
    CHECK_OK(CreateSharedMemoryRegion(key, 128, &fd));
    void* addr = nullptr;
    CHECK_OK(MapSharedMemory(fd, 0, 128, &addr));
    CHECK_OK(d->RegisterShm("it_out", key, 128));

    SimpleRequest req;
    InferRequestedOutput out0("OUTPUT0");
    CHECK_OK(out0.SetSharedMemory("it_out", 64, 0));
    std::unique_ptr<InferResult> result;
    CHECK_OK(d->Infer(InferOptions("simple"), req.inputs(), {&out0},
                      &result));
    REQUIRE(result != nullptr);
    // data landed in the region, not inline
    const int32_t* add = reinterpret_cast<const int32_t*>(addr);
    for (int i = 0; i < 16; ++i) CHECK_EQ(add[i], i + 1);

    CHECK_OK(d->UnregisterShm("it_out"));
    CHECK_OK(UnmapSharedMemory(addr, 128));
    CHECK_OK(CloseSharedMemory(fd));
    CHECK_OK(UnlinkSharedMemoryRegion(key));
  }
}

TEST_CASE("integration: unregistered shm region fails cleanly") {
  for (auto& d : MakeDrivers()) {
    InferInput input0("INPUT0", {1, 16}, "INT32");
    CHECK_OK(input0.SetSharedMemory("never_registered", 64));
    SimpleRequest req;
    std::unique_ptr<InferResult> result;
    Error err = d->Infer(InferOptions("simple"), {&input0, &req.input1}, {},
                         &result);
    bool failed = !err.IsOk() ||
                  (result != nullptr && !result->RequestStatus().IsOk());
    CHECK(failed);
  }
}

// -- sequences ----------------------------------------------------------------

TEST_CASE("integration: sequence accumulates state across requests") {
  for (auto& d : MakeDrivers()) {
    const uint64_t seq = 9000 + (d->name()[0] == 'g' ? 1 : 2);
    int32_t expected = 0;
    for (int step = 0; step < 3; ++step) {
      int32_t value = step + 1;
      expected += value;
      InferInput input("INPUT", {1}, "INT32");
      input.AppendRaw(reinterpret_cast<uint8_t*>(&value), sizeof(value));
      InferOptions options("sequence_accumulate");
      options.sequence_id = seq;
      options.sequence_start = step == 0;
      options.sequence_end = step == 2;
      std::unique_ptr<InferResult> result;
      CHECK_OK(d->Infer(options, {&input}, {}, &result));
      REQUIRE(result != nullptr);
      const uint8_t* buf = nullptr;
      size_t byte_size = 0;
      CHECK_OK(result->RawData("OUTPUT", &buf, &byte_size));
      REQUIRE(byte_size == sizeof(int32_t));
      CHECK_EQ(*reinterpret_cast<const int32_t*>(buf), expected);
    }
  }
}

// -- timeout behavior ---------------------------------------------------------

TEST_CASE("integration: expired client timeout errors, connection recovers") {
  for (auto& d : MakeDrivers()) {
    // A server-side 500 ms execution delay against a 50 ms client
    // deadline: expiry is deterministic (a bare 1 us deadline can race a
    // fast loopback response, which is a legitimate success).
    std::vector<float> data{1.0f, 2.0f};
    InferInput input("INPUT0", {2}, "FP32");
    input.AppendRaw(reinterpret_cast<uint8_t*>(data.data()),
                    data.size() * sizeof(float));
    InferOptions options("identity_fp32");
    options.parameters["delay_ms"] = "500";
    options.client_timeout_us = 50000;
    const auto start = std::chrono::steady_clock::now();
    std::unique_ptr<InferResult> result;
    Error err = d->Infer(options, {&input}, {}, &result);
    const auto elapsed = std::chrono::duration_cast<std::chrono::milliseconds>(
        std::chrono::steady_clock::now() - start);
    bool failed = !err.IsOk() ||
                  (result != nullptr && !result->RequestStatus().IsOk());
    CHECK(failed);
    CHECK(elapsed.count() < 450);  // failed at the deadline, not at 500 ms
    // The same driver serves the next request fine.
    SimpleRequest req;
    InferOptions ok_options("simple");
    std::unique_ptr<InferResult> ok_result;
    CHECK_OK(d->Infer(ok_options, req.inputs(), {}, &ok_result));
    REQUIRE(ok_result != nullptr);
    CheckSimpleResult(ok_result.get());
  }
}

// -- model control ------------------------------------------------------------

TEST_CASE("integration: unload/load cycle changes model readiness") {
  for (auto& d : MakeDrivers()) {
    bool ready = false;
    CHECK_OK(d->ModelReady("identity_fp32", &ready));
    CHECK(ready);
    CHECK_OK(d->Unload("identity_fp32"));
    bool after_unload = true;
    Error err = d->ModelReady("identity_fp32", &after_unload);
    CHECK((!err.IsOk() || !after_unload));
    CHECK_OK(d->Load("identity_fp32"));
    bool after_load = false;
    CHECK_OK(d->ModelReady("identity_fp32", &after_load));
    CHECK(after_load);
  }
}

// -- statistics + trace -------------------------------------------------------

TEST_CASE("integration: statistics success count increments") {
  for (auto& d : MakeDrivers()) {
    uint64_t before = 0;
    CHECK_OK(d->StatsSuccessCount("simple", &before));
    SimpleRequest req;
    std::unique_ptr<InferResult> result;
    CHECK_OK(d->Infer(InferOptions("simple"), req.inputs(), {}, &result));
    uint64_t after = 0;
    CHECK_OK(d->StatsSuccessCount("simple", &after));
    CHECK(after >= before + 1);
  }
}

TEST_CASE("integration: trace settings update round trips") {
  for (auto& d : MakeDrivers()) {
    CHECK_OK(d->UpdateTraceLevel("TIMESTAMPS"));
    CHECK_OK(d->UpdateTraceLevel("OFF"));
  }
}

// -- gRPC-only behaviors ------------------------------------------------------

TEST_CASE("integration: grpc streaming decoupled model yields N responses") {
  GrpcDriver driver;
  std::mutex mu;
  std::condition_variable cv;
  std::vector<int32_t> got;
  bool finished = false;
  CHECK_OK(driver.client->StartStream(
      [&](InferResult* raw) {
        std::unique_ptr<InferResult> result(raw);
        std::lock_guard<std::mutex> lk(mu);
        const uint8_t* buf = nullptr;
        size_t byte_size = 0;
        if (result->RequestStatus().IsOk() &&
            result->RawData("OUT", &buf, &byte_size).IsOk() &&
            byte_size == sizeof(int32_t)) {
          got.push_back(*reinterpret_cast<const int32_t*>(buf));
        }
        if (got.size() >= 3) finished = true;
        cv.notify_all();
      }));
  std::vector<int32_t> values{5, 6, 7};
  InferInput input("IN", {3}, "INT32");
  input.AppendRaw(reinterpret_cast<uint8_t*>(values.data()),
                  values.size() * sizeof(int32_t));
  CHECK_OK(driver.client->AsyncStreamInfer(InferOptions("repeat_int32"),
                                           {&input}));
  {
    std::unique_lock<std::mutex> lk(mu);
    REQUIRE(cv.wait_for(lk, std::chrono::seconds(30),
                        [&] { return finished; }));
  }
  CHECK_OK(driver.client->StopStream());
  REQUIRE(got.size() >= 3);
  CHECK_EQ(got[0], 5);
  CHECK_EQ(got[1], 6);
  CHECK_EQ(got[2], 7);
}

TEST_CASE("integration: grpc request compression (deflate) still infers") {
  GrpcDriver driver;
  CHECK_OK(driver.client->SetCompression("deflate"));
  SimpleRequest req;
  InferResult* raw = nullptr;
  CHECK_OK(driver.client->Infer(&raw, InferOptions("simple"), req.inputs()));
  std::unique_ptr<InferResult> result(raw);
  CheckSimpleResult(result.get());
}

TEST_CASE("integration: concurrent clients from multiple threads") {
  std::atomic<int> failures{0};
  std::vector<std::thread> threads;
  for (int t = 0; t < 8; ++t) {
    threads.emplace_back([&failures] {
      GrpcDriver driver;
      for (int i = 0; i < 50; ++i) {
        SimpleRequest req;
        std::unique_ptr<InferResult> result;
        Error err =
            driver.Infer(InferOptions("simple"), req.inputs(), {}, &result);
        if (!err.IsOk() || result == nullptr ||
            !result->RequestStatus().IsOk()) {
          failures++;
        }
      }
    });
  }
  for (auto& thread : threads) thread.join();
  CHECK_EQ(failures.load(), 0);
}

// -- leak soaks (reference memory_leak_test.cc role) -------------------------

TEST_CASE("integration: grpc soak shows bounded RSS growth") {
  GrpcDriver driver;
  SimpleRequest req;
  // Warm every allocator pool first, then measure.
  for (int i = 0; i < 500; ++i) {
    std::unique_ptr<InferResult> result;
    driver.Infer(InferOptions("simple"), req.inputs(), {}, &result);
  }
  const size_t before_kb = RssKb();
  for (int i = 0; i < 10000; ++i) {
    std::unique_ptr<InferResult> result;
    Error err =
        driver.Infer(InferOptions("simple"), req.inputs(), {}, &result);
    CHECK(err.IsOk());
    if (!err.IsOk()) break;
  }
  const size_t after_kb = RssKb();
  // 10k tiny inferences must not grow the client by more than ~16 MiB.
  CHECK(after_kb < before_kb + 16 * 1024);
}

TEST_CASE("integration: http soak shows bounded RSS growth") {
  HttpDriver driver;
  SimpleRequest req;
  for (int i = 0; i < 200; ++i) {
    std::unique_ptr<InferResult> result;
    driver.Infer(InferOptions("simple"), req.inputs(), {}, &result);
  }
  const size_t before_kb = RssKb();
  for (int i = 0; i < 5000; ++i) {
    std::unique_ptr<InferResult> result;
    Error err =
        driver.Infer(InferOptions("simple"), req.inputs(), {}, &result);
    CHECK(err.IsOk());
    if (!err.IsOk()) break;
  }
  const size_t after_kb = RssKb();
  CHECK(after_kb < before_kb + 16 * 1024);
}

TEST_CASE("integration: async chain soak shows bounded RSS growth") {
  GrpcDriver driver;
  SimpleRequest req;
  std::mutex mu;
  std::condition_variable cv;
  int outstanding = 0;
  auto issue_one = [&] {
    {
      std::lock_guard<std::mutex> lk(mu);
      outstanding++;
    }
    driver.client->AsyncInfer(
        [&](InferResult* raw) {
          delete raw;
          std::lock_guard<std::mutex> lk(mu);
          outstanding--;
          cv.notify_all();
        },
        InferOptions("simple"), req.inputs());
  };
  for (int i = 0; i < 300; ++i) issue_one();
  {
    std::unique_lock<std::mutex> lk(mu);
    cv.wait_for(lk, std::chrono::seconds(60),
                [&] { return outstanding == 0; });
  }
  const size_t before_kb = RssKb();
  for (int batch = 0; batch < 20; ++batch) {
    for (int i = 0; i < 250; ++i) issue_one();
    std::unique_lock<std::mutex> lk(mu);
    cv.wait_for(lk, std::chrono::seconds(60),
                [&] { return outstanding == 0; });
  }
  const size_t after_kb = RssKb();
  CHECK(after_kb < before_kb + 16 * 1024);
}

int main() {
  std::printf("integration_tests: starting server...\n");
  std::fflush(stdout);
  if (!Server().Start()) {
    std::printf("integration_tests: failed to start the server\n");
    return 1;
  }
  std::printf("integration_tests: server up http=%d grpc=%d\n",
              Server().http_port, Server().grpc_port);
  std::fflush(stdout);
  int rc = ctest::RunAll();
  Server().Stop();
  return rc;
}
