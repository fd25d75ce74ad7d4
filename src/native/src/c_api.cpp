// C ABI for the native core — the bridge layer (L3') that plays the role of
// the reference's JNI files. Objects cross the boundary as opaque int64
// handles exactly like the reference's jlong pointer-handles
// (RowConversionJni.cpp:31-36, NativeParquetJni.cpp:547), but routed
// through a registry so stale handles fail cleanly instead of crashing.
// Errors follow the reference's CATCH_STD shape (NativeParquetJni.cpp:549):
// every entry point catches, stores a message, returns a sentinel; callers
// fetch the message via tpudf_last_error().
//
// Consumed by ctypes (spark_rapids_jni_tpu.runtime.native) and by the JNI
// shim (java/ bridge, built only where a JDK exists).

#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <memory>
#include <mutex>
#include <optional>
#include <string>
#include <unordered_map>
#include <vector>

#include "tpudf/get_json_object.hpp"
#include "tpudf/mapped_file.hpp"
#include "tpudf/orc_reader.hpp"
#include "tpudf/parquet_footer.hpp"
#include "tpudf/parquet_reader.hpp"
#include "tpudf/row_conversion.hpp"

namespace {

thread_local std::string g_last_error;

void set_error(std::string msg) { g_last_error = std::move(msg); }

// Generic handle registry: int64 ids -> owned objects. ids start at 1; 0 is
// the null/error sentinel (matching the reference returning 0 on failure).
// Lookups hand out shared_ptr so a concurrent close (e.g. Python GC calling
// __del__ on another thread while ctypes has released the GIL) cannot free
// an object mid-use — the last owner wins.
template <class T>
class Registry {
 public:
  int64_t put(std::shared_ptr<T> obj) {
    std::lock_guard<std::mutex> lock(mu_);
    int64_t id = next_++;
    map_[id] = std::move(obj);
    return id;
  }

  std::shared_ptr<T> get(int64_t id) {
    std::lock_guard<std::mutex> lock(mu_);
    auto it = map_.find(id);
    return it == map_.end() ? nullptr : it->second;
  }

  bool erase(int64_t id) {
    std::lock_guard<std::mutex> lock(mu_);
    return map_.erase(id) > 0;
  }

  int64_t size() {
    std::lock_guard<std::mutex> lock(mu_);
    return static_cast<int64_t>(map_.size());
  }

 private:
  std::mutex mu_;
  std::unordered_map<int64_t, std::shared_ptr<T>> map_;
  int64_t next_ = 1;
};

Registry<tpudf::parquet::Footer>& footers() {
  static Registry<tpudf::parquet::Footer> r;
  return r;
}

Registry<tpudf::parquet::ReadResult>& reads() {
  static Registry<tpudf::parquet::ReadResult> r;
  return r;
}

Registry<tpudf::orc::OrcResult>& orc_reads() {
  static Registry<tpudf::orc::OrcResult> r;
  return r;
}

}  // namespace

extern "C" {

char const* tpudf_last_error() { return g_last_error.c_str(); }

// Parse + prune + filter in one call, mirroring the readAndFilter JNI entry
// (reference NativeParquetJni.cpp:499-550). Returns a footer handle, 0 on
// error.
int64_t tpudf_footer_read_and_filter(uint8_t const* buf, uint64_t len,
                                     int64_t part_offset, int64_t part_length,
                                     char const* const* names,
                                     int32_t const* num_children,
                                     int32_t n_names,
                                     int32_t parent_num_children,
                                     int32_t ignore_case) {
  try {
    auto footer = std::make_shared<tpudf::parquet::Footer>(
        tpudf::parquet::Footer::parse(buf, len));
    std::vector<std::string> name_vec;
    std::vector<int32_t> child_vec;
    name_vec.reserve(n_names);
    child_vec.reserve(n_names);
    for (int32_t k = 0; k < n_names; ++k) {
      name_vec.emplace_back(names[k]);
      child_vec.push_back(num_children[k]);
    }
    // Order matters: the midpoint filter reads the file's first column, so
    // row-group filtering runs between schema pruning and chunk gathering
    // (reference NativeParquetJni.cpp:524-545).
    footer->prune_columns(name_vec, child_vec, parent_num_children,
                          ignore_case != 0);
    if (part_length >= 0) {
      footer->filter_row_groups(part_offset, part_length);
    }
    footer->filter_columns();
    return footers().put(std::move(footer));
  } catch (std::exception const& e) {
    set_error(e.what());
    return 0;
  }
}

int64_t tpudf_footer_num_rows(int64_t handle) {
  try {
    auto f = footers().get(handle);
    if (f == nullptr) throw std::invalid_argument("invalid footer handle");
    return f->num_rows();
  } catch (std::exception const& e) {
    set_error(e.what());
    return -1;
  }
}

int32_t tpudf_footer_num_columns(int64_t handle) {
  try {
    auto f = footers().get(handle);
    if (f == nullptr) throw std::invalid_argument("invalid footer handle");
    return f->num_columns();
  } catch (std::exception const& e) {
    set_error(e.what());
    return -1;
  }
}

// What a filtered footer kept, in the file's own numbering (what
// tpudf_parquet_read* takes as rgs / cols): the surviving row groups with
// their row counts, then the pruned leaves in request order. Each returns
// the count (which may exceed `cap`: nothing past `cap` is written), -1
// on error.
int32_t tpudf_footer_row_groups(int64_t handle, int32_t* index,
                                int64_t* num_rows, int32_t cap) {
  try {
    auto f = footers().get(handle);
    if (f == nullptr) throw std::invalid_argument("invalid footer handle");
    auto const& kept = f->kept_row_groups();
    auto rows = f->row_group_rows();
    if (rows.size() != kept.size()) {
      throw std::logic_error("row group bookkeeping out of step");
    }
    int32_t n = static_cast<int32_t>(kept.size());
    for (int32_t i = 0; i < n && i < cap; ++i) {
      index[i] = kept[i];
      num_rows[i] = rows[i];
    }
    return n;
  } catch (std::exception const& e) {
    set_error(e.what());
    return -1;
  }
}

// For each kept leaf: request[k] its position among the request's leaves,
// leaf_index[k] the file's leaf index, meta[5k..] physical, converted
// (-1 = absent), scale, type_length, repetition.
int32_t tpudf_footer_leaves(int64_t handle, int32_t* request,
                            int32_t* leaf_index, int32_t* meta, int32_t cap) {
  try {
    auto f = footers().get(handle);
    if (f == nullptr) throw std::invalid_argument("invalid footer handle");
    auto const& kept = f->kept_leaves();
    auto const& asked = f->kept_requests();
    auto leaves = f->leaves();
    if (leaves.size() != kept.size() || asked.size() != kept.size()) {
      throw std::logic_error("footer was not pruned by name");
    }
    int32_t n = static_cast<int32_t>(kept.size());
    for (int32_t i = 0; i < n && i < cap; ++i) {
      request[i] = asked[i];
      leaf_index[i] = kept[i];
      meta[5 * i + 0] = leaves[i].physical;
      meta[5 * i + 1] = leaves[i].converted;
      meta[5 * i + 2] = leaves[i].scale;
      meta[5 * i + 3] = leaves[i].type_length;
      meta[5 * i + 4] = leaves[i].repetition;
    }
    return n;
  } catch (std::exception const& e) {
    set_error(e.what());
    return -1;
  }
}

// The leaves the file's schema had before any prune.
int32_t tpudf_footer_file_leaves(int64_t handle) {
  auto f = footers().get(handle);
  if (f == nullptr) {
    set_error("invalid footer handle");
    return -1;
  }
  return f->file_leaves();
}

// Compressed bytes of the column chunks the footer kept; -1 on error.
int64_t tpudf_footer_compressed_bytes(int64_t handle) {
  auto f = footers().get(handle);
  if (f == nullptr) {
    set_error("invalid footer handle");
    return -1;
  }
  return f->compressed_bytes();
}

// Serialize with PAR1 framing into a malloc'd buffer the caller frees with
// tpudf_free_buffer. Returns 0 on success.
int32_t tpudf_footer_serialize(int64_t handle, uint8_t** out,
                               uint64_t* out_len) {
  try {
    auto f = footers().get(handle);
    if (f == nullptr) throw std::invalid_argument("invalid footer handle");
    std::string framed = f->serialize_framed();
    *out = static_cast<uint8_t*>(std::malloc(framed.size()));
    if (*out == nullptr) throw std::bad_alloc();
    std::memcpy(*out, framed.data(), framed.size());
    *out_len = framed.size();
    return 0;
  } catch (std::exception const& e) {
    set_error(e.what());
    return -1;
  }
}

void tpudf_free_buffer(uint8_t* buf) { std::free(buf); }

int32_t tpudf_footer_close(int64_t handle) {
  if (!footers().erase(handle)) {
    set_error("invalid footer handle");
    return -1;
  }
  return 0;
}

// ---- Parquet data reader (chunked at row-group granularity) ---------------

// Decode selected columns / row groups of an in-memory Parquet file into an
// Arrow-layout host result. A null cols/rgs pointer selects all; a non-null
// pointer with count 0 selects none. Returns a read handle, 0 on error.
int64_t tpudf_parquet_read(uint8_t const* buf, uint64_t len,
                           int32_t const* cols, int32_t n_cols,
                           int32_t const* rgs, int32_t n_rgs) {
  try {
    std::optional<std::vector<int32_t>> col_vec;
    if (cols != nullptr) col_vec.emplace(cols, cols + n_cols);
    std::optional<std::vector<int32_t>> rg_vec;
    if (rgs != nullptr) rg_vec.emplace(rgs, rgs + n_rgs);
    auto res = std::make_shared<tpudf::parquet::ReadResult>(
        tpudf::parquet::read_file(buf, len, col_vec, rg_vec));
    return reads().put(std::move(res));
  } catch (std::exception const& e) {
    set_error(e.what());
    return 0;
  }
}

// Storage->decode path without host-visible materialization: mmap the file
// read-only and decode selected columns/row groups straight out of the
// mapping — the cuFile/GDS role (reference CMakeLists.txt:200-222: a direct
// storage->device staging path that bypasses caller-managed buffers). The
// page cursor touches only the byte ranges of the requested chunks, so a
// chunked read of a large file never faults in the rest.
int64_t tpudf_parquet_read_path(char const* path, int32_t const* cols,
                                int32_t n_cols, int32_t const* rgs,
                                int32_t n_rgs) {
  try {
    tpudf::MappedFile map(path);  // RAII mmap; throws with errno detail
    std::optional<std::vector<int32_t>> col_vec;
    if (cols != nullptr) col_vec.emplace(cols, cols + n_cols);
    std::optional<std::vector<int32_t>> rg_vec;
    if (rgs != nullptr) rg_vec.emplace(rgs, rgs + n_rgs);
    auto res = std::make_shared<tpudf::parquet::ReadResult>(
        tpudf::parquet::read_file(map.data(), map.size(), col_vec, rg_vec));
    return reads().put(std::move(res));
  } catch (std::exception const& e) {
    set_error(e.what());
    return 0;
  }
}

// Row-group probe over a file path (mmap; footer pages only are touched).
int32_t tpudf_parquet_row_groups_path(char const* path, int64_t* num_rows,
                                      int64_t* byte_size, int32_t cap) {
  try {
    tpudf::MappedFile map(path);
    auto infos = tpudf::parquet::row_group_infos(map.data(), map.size());
    for (int32_t i = 0; i < cap && i < static_cast<int32_t>(infos.size());
         ++i) {
      num_rows[i] = infos[i].num_rows;
      byte_size[i] = infos[i].total_byte_size;
    }
    return static_cast<int32_t>(infos.size());
  } catch (std::exception const& e) {
    set_error(e.what());
    return -1;
  }
}

// Footer probes for planning chunked reads: fills num_rows/byte_size pairs
// for up to `cap` row groups; returns the total count, -1 on error.
int32_t tpudf_parquet_row_groups(uint8_t const* buf, uint64_t len,
                                 int64_t* num_rows, int64_t* byte_size,
                                 int32_t cap) {
  try {
    auto infos = tpudf::parquet::row_group_infos(buf, len);
    for (int32_t i = 0; i < cap && i < static_cast<int32_t>(infos.size());
         ++i) {
      num_rows[i] = infos[i].num_rows;
      byte_size[i] = infos[i].total_byte_size;
    }
    return static_cast<int32_t>(infos.size());
  } catch (std::exception const& e) {
    set_error(e.what());
    return -1;
  }
}

int64_t tpudf_read_num_rows(int64_t handle) {
  auto r = reads().get(handle);
  if (r == nullptr) {
    set_error("invalid read handle");
    return -1;
  }
  return r->num_rows;
}

int32_t tpudf_read_num_columns(int64_t handle) {
  auto r = reads().get(handle);
  if (r == nullptr) {
    set_error("invalid read handle");
    return -1;
  }
  return static_cast<int32_t>(r->columns.size());
}

// Column metadata: meta = [physical, converted, scale, precision,
// type_length, optional, has_validity] (7 int32s); sizes = [data_bytes,
// chars_bytes, num_rows] (3 int64s). Returns 0 on success.
int32_t tpudf_read_col_meta(int64_t handle, int32_t i, int32_t* meta,
                            int64_t* sizes) {
  auto r = reads().get(handle);
  if (r == nullptr || i < 0 || i >= static_cast<int32_t>(r->columns.size())) {
    set_error("invalid read handle or column index");
    return -1;
  }
  auto const& c = r->columns[i];
  meta[0] = c.physical;
  meta[1] = c.converted;
  meta[2] = c.scale;
  meta[3] = c.precision;
  meta[4] = c.type_length;
  meta[5] = c.optional ? 1 : 0;
  meta[6] = c.validity.empty() ? 0 : 1;
  sizes[0] = static_cast<int64_t>(c.data.size());
  sizes[1] = static_cast<int64_t>(c.chars.size());
  sizes[2] = c.num_rows;
  return 0;
}

// Extended metadata (nested-aware): meta = [physical, converted, scale,
// precision, type_length, optional, has_validity, max_def, max_rep,
// reserved] (10 int32s); sizes = [data_bytes, chars_bytes, num_rows,
// n_levels, n_present] (5 int64s). num_rows counts TOP-LEVEL rows; nested
// leaves carry compact values (n_present) plus n_levels def/rep entries.
int32_t tpudf_read_col_meta2(int64_t handle, int32_t i, int32_t* meta,
                             int64_t* sizes) {
  auto r = reads().get(handle);
  if (r == nullptr || i < 0 || i >= static_cast<int32_t>(r->columns.size())) {
    set_error("invalid read handle or column index");
    return -1;
  }
  auto const& c = r->columns[i];
  meta[0] = c.physical;
  meta[1] = c.converted;
  meta[2] = c.scale;
  meta[3] = c.precision;
  meta[4] = c.type_length;
  meta[5] = c.optional ? 1 : 0;
  meta[6] = c.validity.empty() ? 0 : 1;
  meta[7] = c.max_def;
  meta[8] = c.max_rep;
  meta[9] = c.is_nested ? 1 : 0;
  sizes[0] = static_cast<int64_t>(c.data.size());
  sizes[1] = static_cast<int64_t>(c.chars.size());
  sizes[2] = c.num_rows;
  sizes[3] = c.n_levels;
  sizes[4] = c.n_present;
  return 0;
}

// Copy out a nested leaf's levels: def_out = uint8[n_levels], rep_out =
// uint8[n_levels] (may be null; required only when max_rep > 0).
int32_t tpudf_read_col_levels(int64_t handle, int32_t i, uint8_t* def_out,
                              uint8_t* rep_out) {
  auto r = reads().get(handle);
  if (r == nullptr || i < 0 || i >= static_cast<int32_t>(r->columns.size())) {
    set_error("invalid read handle or column index");
    return -1;
  }
  auto const& c = r->columns[i];
  if (def_out != nullptr && !c.def_levels.empty()) {
    std::memcpy(def_out, c.def_levels.data(), c.def_levels.size());
  }
  if (rep_out != nullptr && !c.rep_levels.empty()) {
    std::memcpy(rep_out, c.rep_levels.data(), c.rep_levels.size());
  }
  return 0;
}

// Preorder schema-tree dump for nested assembly (tab-separated lines; see
// parquet_reader.hpp). Thread-local copy, valid until this thread's next
// call.
char const* tpudf_read_schema_desc(int64_t handle) {
  thread_local std::string desc_buf;
  auto r = reads().get(handle);
  if (r == nullptr) {
    set_error("invalid read handle");
    return nullptr;
  }
  desc_buf = r->schema_desc;
  return desc_buf.c_str();
}

// Pointer to the column's name (NUL-terminated). The string is copied into
// thread-local storage so a concurrent tpudf_read_close on another thread
// cannot free it out from under the caller — valid until this thread's next
// tpudf_read_col_name call.
char const* tpudf_read_col_name(int64_t handle, int32_t i) {
  thread_local std::string name_buf;
  auto r = reads().get(handle);
  if (r == nullptr || i < 0 || i >= static_cast<int32_t>(r->columns.size())) {
    set_error("invalid read handle or column index");
    return nullptr;
  }
  name_buf = r->columns[i].name;
  return name_buf.c_str();
}

// Copy out column buffers; any destination may be null to skip it.
// data: fixed-width payload; offsets: int32[num_rows+1] (BYTE_ARRAY only);
// chars: string payload; validity: uint8[num_rows]. Returns 0 on success.
int32_t tpudf_read_col_copy(int64_t handle, int32_t i, uint8_t* data,
                            int32_t* offsets, uint8_t* chars,
                            uint8_t* validity) {
  auto r = reads().get(handle);
  if (r == nullptr || i < 0 || i >= static_cast<int32_t>(r->columns.size())) {
    set_error("invalid read handle or column index");
    return -1;
  }
  auto const& c = r->columns[i];
  if (data != nullptr && !c.data.empty()) {
    std::memcpy(data, c.data.data(), c.data.size());
  }
  if (offsets != nullptr && !c.offsets.empty()) {
    std::memcpy(offsets, c.offsets.data(), c.offsets.size() * sizeof(int32_t));
  }
  if (chars != nullptr && !c.chars.empty()) {
    std::memcpy(chars, c.chars.data(), c.chars.size());
  }
  if (validity != nullptr && !c.validity.empty()) {
    std::memcpy(validity, c.validity.data(), c.validity.size());
  }
  return 0;
}

int32_t tpudf_read_close(int64_t handle) {
  if (!reads().erase(handle)) {
    set_error("invalid read handle");
    return -1;
  }
  return 0;
}

// ---- ORC reader (chunked at stripe granularity) ---------------------------

int64_t tpudf_orc_read(uint8_t const* buf, uint64_t len, int32_t const* cols,
                       int32_t n_cols, int32_t const* stripes,
                       int32_t n_stripes) {
  try {
    std::optional<std::vector<int32_t>> col_vec;
    if (cols != nullptr) col_vec.emplace(cols, cols + n_cols);
    std::optional<std::vector<int32_t>> st_vec;
    if (stripes != nullptr) st_vec.emplace(stripes, stripes + n_stripes);
    auto res = std::make_shared<tpudf::orc::OrcResult>(
        tpudf::orc::read_file(buf, len, col_vec, st_vec));
    return orc_reads().put(std::move(res));
  } catch (std::exception const& e) {
    set_error(e.what());
    return 0;
  }
}

// ORC half of the mmap storage route (cuFile/GDS role, mirroring
// tpudf_parquet_read_path): decode straight out of a read-only mapping —
// stripe-selective chunked reads fault in only the selected byte ranges.
int64_t tpudf_orc_read_path(char const* path, int32_t const* cols,
                            int32_t n_cols, int32_t const* stripes,
                            int32_t n_stripes) {
  try {
    tpudf::MappedFile map(path);
    std::optional<std::vector<int32_t>> col_vec;
    if (cols != nullptr) col_vec.emplace(cols, cols + n_cols);
    std::optional<std::vector<int32_t>> st_vec;
    if (stripes != nullptr) st_vec.emplace(stripes, stripes + n_stripes);
    auto res = std::make_shared<tpudf::orc::OrcResult>(
        tpudf::orc::read_file(map.data(), map.size(), col_vec, st_vec));
    return orc_reads().put(std::move(res));
  } catch (std::exception const& e) {
    set_error(e.what());
    return 0;
  }
}

// Stripe probe over a file path (mmap; tail pages only are touched).
int32_t tpudf_orc_stripes_path(char const* path, int64_t* num_rows,
                               int64_t* byte_size, int32_t cap) {
  try {
    tpudf::MappedFile map(path);
    auto infos = tpudf::orc::stripe_infos(map.data(), map.size());
    for (int32_t i = 0; i < cap && i < static_cast<int32_t>(infos.size());
         ++i) {
      num_rows[i] = infos[i].num_rows;
      byte_size[i] = infos[i].data_bytes;
    }
    return static_cast<int32_t>(infos.size());
  } catch (std::exception const& e) {
    set_error(e.what());
    return -1;
  }
}

int32_t tpudf_orc_stripes(uint8_t const* buf, uint64_t len, int64_t* num_rows,
                          int64_t* byte_size, int32_t cap) {
  try {
    auto infos = tpudf::orc::stripe_infos(buf, len);
    for (int32_t i = 0; i < cap && i < static_cast<int32_t>(infos.size());
         ++i) {
      num_rows[i] = infos[i].num_rows;
      byte_size[i] = infos[i].data_bytes;
    }
    return static_cast<int32_t>(infos.size());
  } catch (std::exception const& e) {
    set_error(e.what());
    return -1;
  }
}

int32_t tpudf_orc_num_columns(int64_t handle) {
  auto r = orc_reads().get(handle);
  if (r == nullptr) {
    set_error("invalid orc read handle");
    return -1;
  }
  return static_cast<int32_t>(r->columns.size());
}

int64_t tpudf_orc_num_rows(int64_t handle) {
  auto r = orc_reads().get(handle);
  if (r == nullptr) {
    set_error("invalid orc read handle");
    return -1;
  }
  return r->num_rows;
}

// meta = [kind, precision, scale, has_validity] (4 int32); sizes =
// [num_rows, chars_bytes] (2 int64).
int32_t tpudf_orc_col_meta(int64_t handle, int32_t i, int32_t* meta,
                           int64_t* sizes) {
  auto r = orc_reads().get(handle);
  if (r == nullptr || i < 0 || i >= static_cast<int32_t>(r->columns.size())) {
    set_error("invalid orc read handle or column index");
    return -1;
  }
  auto const& c = r->columns[i];
  meta[0] = c.kind;
  meta[1] = c.precision;
  meta[2] = c.scale;
  meta[3] = c.validity.empty() ? 0 : 1;
  sizes[0] = c.num_rows;
  sizes[1] = static_cast<int64_t>(c.chars.size());
  return 0;
}

// the unique StripeFooter.writerTimezone of the decoded stripes ("" =
// none recorded / UTC-family): TIMESTAMP payloads are wall-clock micros
// in this zone and the caller owns the tz-database conversion.
char const* tpudf_orc_writer_timezone(int64_t handle) {
  thread_local std::string tz_buf;
  auto r = orc_reads().get(handle);
  if (r == nullptr) {
    set_error("invalid orc read handle");
    return nullptr;
  }
  tz_buf = r->writer_timezone;
  return tz_buf.c_str();
}

char const* tpudf_orc_col_name(int64_t handle, int32_t i) {
  thread_local std::string name_buf;
  auto r = orc_reads().get(handle);
  if (r == nullptr || i < 0 || i >= static_cast<int32_t>(r->columns.size())) {
    set_error("invalid orc read handle or column index");
    return nullptr;
  }
  name_buf = r->columns[i].name;
  return name_buf.c_str();
}

// data: int64[num_rows] (always, incl. float bit patterns); offsets/chars
// only for string kinds; validity uint8[num_rows]. Null dests skip.
int32_t tpudf_orc_col_copy(int64_t handle, int32_t i, int64_t* data,
                           int32_t* offsets, uint8_t* chars,
                           uint8_t* validity) {
  auto r = orc_reads().get(handle);
  if (r == nullptr || i < 0 || i >= static_cast<int32_t>(r->columns.size())) {
    set_error("invalid orc read handle or column index");
    return -1;
  }
  auto const& c = r->columns[i];
  if (data != nullptr && !c.data.empty()) {
    std::memcpy(data, c.data.data(), c.data.size() * sizeof(int64_t));
  }
  if (offsets != nullptr && !c.offsets.empty()) {
    std::memcpy(offsets, c.offsets.data(), c.offsets.size() * sizeof(int32_t));
  }
  if (chars != nullptr && !c.chars.empty()) {
    std::memcpy(chars, c.chars.data(), c.chars.size());
  }
  if (validity != nullptr && !c.validity.empty()) {
    std::memcpy(validity, c.validity.data(), c.validity.size());
  }
  return 0;
}

int32_t tpudf_orc_close(int64_t handle) {
  if (!orc_reads().erase(handle)) {
    set_error("invalid orc read handle");
    return -1;
  }
  return 0;
}

// RLEv2 decode hook for spec-vector tests.
int32_t tpudf_orc_decode_rle2(uint8_t const* buf, uint64_t len, int64_t count,
                              int32_t is_signed, int64_t* out) {
  try {
    auto vals = tpudf::orc::decode_rle_v2(buf, len, count, is_signed != 0);
    std::memcpy(out, vals.data(), vals.size() * sizeof(int64_t));
    return 0;
  } catch (std::exception const& e) {
    set_error(e.what());
    return -1;
  }
}

// ---- host packed-row codec (C1' native half) ------------------------------

// Layout probe: fills starts[n_cols], returns row_size (or -1 on error).
int32_t tpudf_rows_layout(int32_t const* sizes, int32_t n_cols,
                          int32_t* starts) {
  try {
    std::vector<int32_t> sz(sizes, sizes + n_cols);
    auto layout = tpudf::rows::fixed_width_layout(sz);
    for (int32_t i = 0; i < n_cols; ++i) starts[i] = layout.start[i];
    return layout.row_size;
  } catch (std::exception const& e) {
    set_error(e.what());
    return -1;
  }
}

int32_t tpudf_to_rows(uint8_t const* const* col_data,
                      uint8_t const* const* col_valid, int32_t const* sizes,
                      int32_t n_cols, int64_t n_rows, uint8_t* out) {
  try {
    std::vector<int32_t> sz(sizes, sizes + n_cols);
    tpudf::rows::to_rows(col_data, col_valid, sz, n_rows, out);
    return 0;
  } catch (std::exception const& e) {
    set_error(e.what());
    return -1;
  }
}

int32_t tpudf_from_rows(uint8_t const* rows_buf, int64_t n_rows,
                        int32_t const* sizes, int32_t n_cols,
                        uint8_t* const* col_data, uint8_t* const* col_valid) {
  try {
    std::vector<int32_t> sz(sizes, sizes + n_cols);
    tpudf::rows::from_rows(rows_buf, n_rows, sz, col_data, col_valid);
    return 0;
  } catch (std::exception const& e) {
    set_error(e.what());
    return -1;
  }
}

// ---- get_json_object ------------------------------------------------------

// Extract `path` from each row of an Arrow string column. out_chars is
// malloc'd (free with tpudf_free_buffer); out_offsets has n_rows+1 slots,
// out_valid n_rows. Returns 0, or -1 on error (e.g. unsupported path).
int32_t tpudf_get_json_object(uint8_t const* chars, int32_t const* offsets,
                              uint8_t const* valid, int64_t n_rows,
                              char const* path, uint8_t** out_chars,
                              int64_t* out_chars_len, int32_t* out_offsets,
                              uint8_t* out_valid) {
  try {
    // Compile the path once for the whole column — also surfaces bad-path
    // errors even when every row is NULL (Spark's analyzer behavior).
    auto const steps = tpudf::json::parse_path(path);
    std::string result;
    out_offsets[0] = 0;
    for (int64_t r = 0; r < n_rows; ++r) {
      std::optional<std::string> match;
      if (valid == nullptr || valid[r]) {
        std::string_view row(
            reinterpret_cast<char const*>(chars) + offsets[r],
            static_cast<size_t>(offsets[r + 1] - offsets[r]));
        match = tpudf::json::get_json_object(row, steps);
      }
      if (match.has_value()) {
        result += *match;
        out_valid[r] = 1;
      } else {
        out_valid[r] = 0;
      }
      if (result.size() > static_cast<size_t>(INT32_MAX)) {
        throw std::overflow_error(
            "get_json_object output exceeds 2^31 chars");
      }
      out_offsets[r + 1] = static_cast<int32_t>(result.size());
    }
    *out_chars = static_cast<uint8_t*>(std::malloc(result.size() + 1));
    if (*out_chars == nullptr) throw std::bad_alloc();
    std::memcpy(*out_chars, result.data(), result.size());
    *out_chars_len = static_cast<int64_t>(result.size());
    return 0;
  } catch (std::exception const& e) {
    set_error(e.what());
    return -1;
  }
}

// Open-handle count — backs leak-check tests, the moral equivalent of the
// reference's refcount leak-debugging flag (pom.xml:86,436).
int64_t tpudf_open_handles() {
  return footers().size() + reads().size() + orc_reads().size();
}
}
